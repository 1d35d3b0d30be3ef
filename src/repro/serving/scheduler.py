"""Slot scheduler: admission + fixed-shape batch construction.

Every engine iteration is one of two fixed shapes, so the jitted model step
compiles exactly twice and never again:

  * a chunk-shaped batch ``(slots, prefill_chunk)`` — the next chunk of
    every request still processing its prompt, and (``mixed=True``, the
    default) every decoding request riding the same call with
    ``n_valid = 1``;
  * a DECODE batch ``(slots, 1)`` — the last token of every decoding
    request, used whenever no prefill work pends so the thin-M
    decode-specialized kernel tiles keep firing.

Rows for idle/finished slots (and the padding tail of a short chunk) carry
``n_valid = 0`` and do not advance their cursor.  ``ScheduledBatch.row_kinds``
records, per participating request, whether its row is a prompt chunk
("prefill") or a single generated token ("decode") — the engine's unified
postprocess and per-row metrics attribution key off it.

Fairness: admission is (priority, FIFO).  With ``mixed=True`` a running
decode advances on EVERY iteration, so a stream of long prompts cannot
stall it at all (the historical decode stall).  With ``mixed=False`` the
scheduler falls back to strict whole-batch alternation between the two
kinds (``interleave=True``), which bounds — but does not remove — the
stall at one chunk call per decode token.  Admission into a freed slot
happens before every batch, so a waiting request is picked up at the first
opportunity — together with FIFO order this bounds every request's wait by
the work admitted before it (no starvation).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.serving import telemetry
from repro.serving.kv_pool import SlotPool
from repro.serving.request import Request, RequestQueue, RequestState


@dataclasses.dataclass
class ScheduledBatch:
    """One fixed-shape engine iteration."""

    #: "prefill" | "decode" | "mixed" (chunk-shaped, both row kinds) |
    #: "draft" (thin speculative draft call) | "spec" (chunk-shaped verify)
    kind: str
    tokens: np.ndarray  # (slots, C) int32
    n_valid: np.ndarray  # (slots,) int32
    rows: list[Request]  # participating requests (their .slot indexes rows)
    #: per entry of ``rows``: "prefill" | "decode" | "verify" (a decoding
    #: request's [last-token, drafts...] row inside a speculative verify
    #: call — n_valid = k_eff + 1 instead of a decode row's 1)
    row_kinds: list[str]


class SlotScheduler:
    def __init__(self, slots: int, prefill_chunk: int,
                 interleave: bool = True, mixed: bool = True) -> None:
        self.slots = slots
        self.prefill_chunk = prefill_chunk
        self.interleave = interleave
        self.mixed = mixed
        self._prefill_turn = True  # alternation state when both kinds pend

    # -- admission -----------------------------------------------------------

    def purge_expired(self, queue: RequestQueue, metrics=None,
                      tracer=None) -> list[Request]:
        """Evict queued requests whose deadline already passed.

        They are terminal (``finish_reason = "deadline"``) without ever
        touching a slot — admitting a request that cannot possibly answer
        inside its latency budget only wastes prefill compute.  The engine
        calls this before every admission pass and returns the expired
        requests from ``step()`` so pollers observe them finishing.
        """
        import time

        expired = queue.purge(lambda r: r.deadline_expired)
        for r in expired:
            r.state = RequestState.FINISHED
            r.finish_reason = "deadline"
            r.t_finish = time.time()
            if metrics is not None:
                metrics.requests_deadline_expired += 1
            if tracer is not None:
                tracer.record("evicted", rid=r.rid, reason="deadline",
                              deadline_ms=r.deadline_ms)
        return expired

    def admit(self, queue: RequestQueue, pool: SlotPool,
              active: dict[int, Request], metrics=None,
              tracer=None) -> list[Request]:
        """Move queued requests into free slots (priority, then FIFO).

        Placement can fail on CAPACITY, not just on slots: the paged pool
        admits only when every block the request can need is reservable.
        The head request is therefore peeked, placed, and only then popped
        — on failure it keeps its queue position and the iteration is
        counted as a ``no_capacity_stalls`` sample (distinct from
        queue-full rejection, which drops work; a stall only delays it).

        A prefix-cache hit comes back with ``req.prefix_hit_tokens`` set
        and the slot cursor pre-advanced; the request enters chunked
        prefill with that much of its prompt already marked done (at least
        one token always remains, to produce its first-token logits).

        Each placement opens a zero-length ``admit`` span (with the
        request's id and queue wait); ``tracer`` (the engine's
        :class:`repro.serving.telemetry.SpanTracer`, when its ring is on)
        records it, and a ``capacity_stall`` event per stalled iteration.
        """
        admitted = []
        stalled = False
        while len(queue):
            if not pool.n_free:
                stalled = True
                break
            req = queue.peek()
            slot = pool.acquire_for(req)
            if slot is None:
                stalled = True
                break
            queue.pop()
            req.slot = slot
            req.prefilled = req.prefix_hit_tokens
            req.state = RequestState.PREFILL
            active[slot] = req
            admitted.append(req)
            telemetry.instant(
                tracer, "admit", rid=req.rid, slot=slot,
                queue_wait_s=round(telemetry.clock() - req.t_queued_mono, 6))
        if stalled:
            if metrics is not None:
                metrics.no_capacity_stalls += 1
            if tracer is not None:
                head = queue.peek()
                tracer.record("capacity_stall",
                              rid=head.rid if head else None,
                              queued=len(queue))
        return admitted

    # -- load accounting -----------------------------------------------------

    def backlog(self, queue: RequestQueue,
                active: dict[int, Request]) -> dict:
        """Work pending on this scheduler, split by phase — the fleet
        router's load-balancing signal.  ``queued`` is admission backlog,
        ``prefilling``/``decoding`` are slot-resident; their sum is the
        number of requests that must finish before a new submit drains."""
        prefilling = sum(r.state == RequestState.PREFILL
                         for r in active.values())
        decoding = sum(r.state == RequestState.DECODE
                       for r in active.values())
        return {"queued": len(queue), "prefilling": prefilling,
                "decoding": decoding,
                "pending": len(queue) + prefilling + decoding}

    # -- batch construction --------------------------------------------------

    def next_batch(self, active: dict[int, Request]) -> ScheduledBatch | None:
        prefilling = [r for r in active.values()
                      if r.state == RequestState.PREFILL]
        decoding = [r for r in active.values()
                    if r.state == RequestState.DECODE]
        if not prefilling and not decoding:
            return None

        if prefilling and decoding:
            if self.mixed:
                return self._chunk_batch(prefilling, decoding)
            do_prefill = self._prefill_turn if self.interleave else True
            self._prefill_turn = not self._prefill_turn
        else:
            do_prefill = bool(prefilling)

        if do_prefill:
            return self._chunk_batch(prefilling, [])
        return self._decode_batch(decoding)

    def _chunk_batch(self, prefilling: list[Request],
                     decoding: list[Request]) -> ScheduledBatch:
        """Chunk-shaped ``(slots, prefill_chunk)`` batch: prompt chunks plus
        (mixed mode) decode rows with ``n_valid = 1``."""
        ch = self.prefill_chunk
        tokens = np.zeros((self.slots, ch), np.int32)
        n_valid = np.zeros((self.slots,), np.int32)
        for r in prefilling:
            n = min(ch, r.prompt_len - r.prefilled)
            tokens[r.slot, :n] = r.prompt[r.prefilled : r.prefilled + n]
            n_valid[r.slot] = n
        for r in decoding:
            tokens[r.slot, 0] = r.generated[-1]
            n_valid[r.slot] = 1
        kind = "mixed" if decoding else "prefill"
        return ScheduledBatch(kind, tokens, n_valid, prefilling + decoding,
                              ["prefill"] * len(prefilling)
                              + ["decode"] * len(decoding))

    def _decode_batch(self, decoding: list[Request]) -> ScheduledBatch:
        tokens = np.zeros((self.slots, 1), np.int32)
        n_valid = np.zeros((self.slots,), np.int32)
        for r in decoding:
            tokens[r.slot, 0] = r.generated[-1]
            n_valid[r.slot] = 1
        return ScheduledBatch("decode", tokens, n_valid, decoding,
                              ["decode"] * len(decoding))

    # -- speculative batches (repro.serving.speculative) ---------------------

    def draft_batch(self, rnd, i: int) -> ScheduledBatch:
        """Thin ``(slots, 1)`` draft call ``i`` of a speculative round.

        Only spec rows still inside their ``k_eff`` participate; everyone
        else (prefill, plain-decode, idle) is ``n_valid = 0`` padding.  The
        engine runs these with the DRAFT parameters, so the jit cache entry
        is (draft structure, thin shape) — the same thin shape slot plain
        decode would have used, never a third one."""
        from repro.serving.speculative import draft_inputs

        tokens, n_valid = draft_inputs(rnd, self.slots, i)
        rows = [row.req for row in rnd.spec_rows if i < row.k_eff]
        return ScheduledBatch("draft", tokens, n_valid, rows,
                              ["draft"] * len(rows))

    def verify_batch(self, rnd) -> ScheduledBatch:
        """The speculative round's single chunk-shaped exact call.

        Three row kinds share the ``(slots, prefill_chunk)`` shape: prompt
        chunks ("prefill", exactly as in :meth:`_chunk_batch`), verify rows
        carrying ``[last-token, d_1..d_k]`` with ``n_valid = k_eff + 1``
        ("verify" — k+1 greedy verdicts in one dispatch, riding the same
        mixed-batch machinery that lets decode rows share chunk calls), and
        budget-exhausted decoders as ordinary ``n_valid = 1`` rows
        ("decode").  Keeping the latter chunk-shaped is what preserves the
        two-compiled-shapes invariant under speculation: the exact
        parameters never see the thin shape."""
        ch = self.prefill_chunk
        tokens = np.zeros((self.slots, ch), np.int32)
        n_valid = np.zeros((self.slots,), np.int32)
        rows: list[Request] = []
        kinds: list[str] = []
        for r in rnd.prefilling:
            n = min(ch, r.prompt_len - r.prefilled)
            tokens[r.slot, :n] = r.prompt[r.prefilled : r.prefilled + n]
            n_valid[r.slot] = n
            rows.append(r)
            kinds.append("prefill")
        for row in rnd.spec_rows:
            r = row.req
            seq = [r.generated[-1]] + row.drafts
            tokens[r.slot, :len(seq)] = seq
            n_valid[r.slot] = len(seq)
            rows.append(r)
            kinds.append("verify")
        for r in rnd.plain:
            tokens[r.slot, 0] = r.generated[-1]
            n_valid[r.slot] = 1
            rows.append(r)
            kinds.append("decode")
        return ScheduledBatch("spec", tokens, n_valid, rows, kinds)
