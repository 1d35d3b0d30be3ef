"""Request lifecycle for the continuous-batching engine.

A :class:`Request` carries one generation job through the state machine

    QUEUED -> PREFILL -> DECODE -> FINISHED
       \\-> REJECTED (admission control)

:class:`RequestQueue` orders admission by (priority, arrival): lower
``priority`` values run first, FIFO within a priority class.
:class:`AdmissionController` bounds queue depth and rejects jobs that can
never fit a slot, so the engine fails fast instead of deadlocking a slot.
"""

from __future__ import annotations

import dataclasses
import enum
import heapq
import itertools
import time
from typing import Callable

from repro.serving import telemetry


class RequestState(enum.Enum):
    QUEUED = "queued"
    PREFILL = "prefill"
    DECODE = "decode"
    FINISHED = "finished"
    REJECTED = "rejected"


@dataclasses.dataclass
class Request:
    """One generation job and its per-request serving telemetry."""

    rid: int
    prompt: list[int]
    max_new_tokens: int
    priority: int = 0  # lower = more urgent; FIFO within a class
    eos_id: int | None = None
    #: per-request latency SLO: wall-clock budget in ms from submission.
    #: None = no deadline.  Expired QUEUED requests are purged before
    #: admission (finish_reason "deadline", never served); running
    #: requests stop at the first emission/prefill boundary past the
    #: budget (partial output kept).
    deadline_ms: float | None = None
    #: streaming hook, called as on_token(request, token) per generated token
    on_token: Callable | None = None

    state: RequestState = RequestState.QUEUED
    reject_reason: str | None = None
    #: prompt tokens whose prefill was skipped by attaching to prefix-cache
    #: blocks at admission (paged KV layout); the cursor starts here
    prefix_hit_tokens: int = 0
    #: memoized sha256 block-hash chain of the prompt (paged layout) — a
    #: capacity-stalled admission retries every engine step and must not
    #: rehash the prompt each time; filled lazily by PagedKVPool
    block_hashes: list | None = dataclasses.field(default=None, repr=False)
    #: recorded by the engine at the moment the stop condition fires
    #: ("length" | "eos"); None while running.  Recorded — not re-derived
    #: from the token tail — because a length-stopped generation whose last
    #: greedy token merely coincides with ``eos_id`` is still a length stop.
    finish_reason: str | None = None
    slot: int | None = None
    prefilled: int = 0  # prompt tokens already processed (chunked prefill)
    generated: list[int] = dataclasses.field(default_factory=list)

    t_submit: float = dataclasses.field(default_factory=time.time)
    #: submission stamp on the span clock (``telemetry.clock``, monotonic):
    #: the queue wait an ``admit`` span carries must not jump with
    #: wall-clock adjustments
    t_queued_mono: float = dataclasses.field(
        default_factory=telemetry.clock, repr=False)
    t_first_token: float | None = None
    t_last_token: float | None = None
    t_finish: float | None = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def finished(self) -> bool:
        return self.state == RequestState.FINISHED

    @property
    def deadline_expired(self) -> bool:
        return (self.deadline_ms is not None
                and (time.time() - self.t_submit) * 1000.0 > self.deadline_ms)

    @property
    def ttft(self) -> float | None:
        """Time to first token (seconds from submit)."""
        if self.t_first_token is None:
            return None
        return self.t_first_token - self.t_submit

    def emit(self, token: int) -> float | None:
        """Record one generated token; returns the inter-token gap in
        seconds (None for the first token) for stall accounting."""
        now = time.time()
        gap = None if self.t_last_token is None else now - self.t_last_token
        if self.t_first_token is None:
            self.t_first_token = now
        self.t_last_token = now
        self.generated.append(token)
        if self.on_token is not None:
            self.on_token(self, token)
        return gap


class RequestQueue:
    """Priority queue with FIFO order inside each priority class."""

    def __init__(self) -> None:
        self._heap: list[tuple[int, int, Request]] = []
        self._seq = itertools.count()

    def push(self, req: Request) -> None:
        heapq.heappush(self._heap, (req.priority, next(self._seq), req))

    def pop(self) -> Request | None:
        if not self._heap:
            return None
        return heapq.heappop(self._heap)[2]

    def peek(self) -> Request | None:
        return self._heap[0][2] if self._heap else None

    def lowest_priority(self) -> int | None:
        """Worst (numerically largest) priority value currently queued."""
        return max(pr for pr, _, _ in self._heap) if self._heap else None

    def evict_lowest(self) -> Request | None:
        """Remove and return the worst queued request: the lowest priority
        class, latest arrival within it (evicting the newest lowest-priority
        job preserves FIFO fairness among its peers)."""
        if not self._heap:
            return None
        i = max(range(len(self._heap)),
                key=lambda j: (self._heap[j][0], self._heap[j][1]))
        req = self._heap[i][2]
        self._heap[i] = self._heap[-1]
        self._heap.pop()
        heapq.heapify(self._heap)
        return req

    def purge(self, pred) -> list[Request]:
        """Remove and return every queued request satisfying ``pred``,
        preserving (priority, FIFO) order among the survivors.  Used for
        deadline expiry: an expired request must not consume a slot."""
        flagged = [bool(pred(e[2])) for e in self._heap]  # evaluate ONCE:
        # a time-based predicate must not flip between the two passes
        if not any(flagged):
            return []
        gone = [e[2] for e, f in zip(self._heap, flagged) if f]
        self._heap = [e for e, f in zip(self._heap, flagged) if not f]
        heapq.heapify(self._heap)
        return gone

    def __len__(self) -> int:
        return len(self._heap)


class AdmissionController:
    """Bounds queue depth and rejects jobs that cannot fit a slot.

    ``max_len`` is the per-slot KV capacity; a prompt must fit when rounded
    up to whole prefill chunks (chunk writes are fixed-shape) AND leave room
    for its generation budget, otherwise the job would stall a slot forever.
    Under the paged KV layout (``kv_block_size``/``kv_blocks`` set) the
    job's worst-case block need must also fit the WHOLE pool — a request
    needing more blocks than exist could never be placed, and leaving it
    queued would wedge the engine behind an eternal capacity stall.
    """

    def __init__(self, max_queue: int, max_len: int, prefill_chunk: int,
                 kv_block_size: int | None = None,
                 kv_blocks: int | None = None) -> None:
        self.max_queue = max_queue
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.kv_block_size = kv_block_size
        self.kv_blocks = kv_blocks

    def check(self, queue: RequestQueue, req: Request) -> tuple[bool, str | None]:
        """Pure admission predicate (no queue mutation).

        A full queue rejects the newcomer only when nothing queued has
        strictly lower priority; otherwise :meth:`admit` makes room by
        evicting the worst queued request — a priority-0 job must never be
        dropped in favour of already-queued best-effort work.
        """
        if req.prompt_len == 0:
            return False, "empty prompt"
        if req.max_new_tokens < 1:
            return False, "max_new_tokens must be >= 1"
        if len(queue) >= self.max_queue:
            worst = queue.lowest_priority()
            if worst is None or worst <= req.priority:
                return False, f"queue full ({self.max_queue})"
        ch = self.prefill_chunk
        padded = ((req.prompt_len + ch - 1) // ch) * ch
        if padded > self.max_len:
            return False, (f"prompt of {req.prompt_len} (padded {padded}) "
                           f"exceeds slot capacity {self.max_len}")
        if req.prompt_len + req.max_new_tokens > self.max_len:
            return False, (f"prompt+generation {req.prompt_len}+"
                           f"{req.max_new_tokens} exceeds slot capacity "
                           f"{self.max_len}")
        if self.kv_blocks is not None:
            bs = self.kv_block_size
            need = (req.prompt_len + req.max_new_tokens + bs - 1) // bs
            if need > self.kv_blocks:
                return False, (f"needs {need} KV blocks; the pool holds "
                               f"{self.kv_blocks}")
        return True, None

    def admit(self, queue: RequestQueue,
              req: Request) -> tuple[bool, str | None, Request | None]:
        """:meth:`check` plus queue-full eviction.

        Returns ``(ok, reason, evicted)``.  When the queue is at capacity
        but holds strictly lower-priority work, the worst queued request is
        removed and returned so the caller can re-reject it (and account
        for the eviction); the newcomer is admitted in its place.
        """
        ok, reason = self.check(queue, req)
        if not ok:
            return False, reason, None
        evicted = None
        if len(queue) >= self.max_queue:
            evicted = queue.evict_lowest()
        return True, None, evicted
