"""Self-verifying speculative decode: the approximate model drafts, the
exact model verifies.

The paper's control-variate scheme hands us two *numerics personalities of
the same weights*: a cheap perforated+CV path and an exact-int8 path, packed
from one checkpoint (`repro.launch.serve.build_serving_params` under two
NumericsSpecs).  That is exactly the draft/verifier pair speculative
decoding wants — with zero extra parameter memory — and it turns
approximation error from an accuracy cost into pure latency headroom:
outputs stay bit-identical to exact-int8 greedy decode, and the draft
acceptance rate becomes a *measurable draft-quality signal* for the CV knob
(closing the loop the error probe opened: the probe reports numeric error,
acceptance reports its argmax-level consequence).

One speculative round, per participating slot
=============================================

State before a round: the request has emitted ``g`` tokens, the last one
``x = generated[-1]`` not yet fed to the model, cursor ``L = plen + g - 1``.

1. **Plan.**  ``k_eff = min(k, budget - 1, chunk - 1)`` where ``budget`` is
   the remaining generation allowance.  The ``budget - 1`` cap guarantees
   the round's emissions (``<= k_eff + 1``) never exceed the budget and
   that every cursor the draft phase writes stays ``<= max_len - 1`` (the
   thin-call fast path in ``_write_plan`` cannot clamp) and inside the
   paged layout's up-front block reservation.  Slots with ``k_eff == 0``
   (one token of budget left) ride the verify call as plain ``n_valid = 1``
   decode rows instead.
2. **Draft.**  ``max(k_eff)`` thin ``(slots, 1)`` calls with the DRAFT
   parameters, each feeding the previous greedy output (``x`` first);
   row ``b`` participates while ``i < k_eff[b]`` and pads with
   ``n_valid = 0`` after.  This writes *approximate* K/V at ``[L, L+k)``
   and collects drafts ``d_1 .. d_k``.
3. **Rollback.**  Cursors retreat to their pre-draft values (a pure cursor
   move — see ``repro.models.lm.rollback_slots``).  The draft K/V above the
   cursor is now masked, and the verify call overwrites it with exact K/V.
4. **Verify.**  ONE chunk-shaped call with the EXACT parameters: verify
   rows carry ``[x, d_1 .. d_k]`` with ``n_valid = k + 1`` (PR 4's
   mixed-batch machinery — decode rows riding the chunk shape — already
   proved chunk-riding rows token-identical to thin calls; on a TPU that
   holds for packed exact params, not for float32 ones, see
   :mod:`repro.serving.fleet`), prefill rows
   their next prompt chunk, plain rows their one token.  Column ``i``'s
   argmax is the exact model's greedy token ``v_{i+1}`` after input ``i``.
5. **Accept.**  ``j`` = longest prefix with ``v_i == d_i``.  The emission
   candidates are ``v_1 .. v_{j+1}`` — the agreeing drafts plus the
   verifier's correction token, all of them *exact-model* outputs, so the
   emitted stream is bit-identical to sequential exact greedy decode by
   induction (every verified position's inputs and attended K/V are the
   accepted exact history).
6. **Stop + final rollback.**  Candidates are emitted one at a time through
   the engine's normal stop check; eos/length can only fire on an emitted
   (= accepted) token — a drafted-but-rejected eos is never seen by the
   stop logic.  The cursor lands at ``L + emitted``; exact K/V beyond it
   (rejected positions, or accepted-but-truncated ones) stays masked until
   overwritten next round.

Compile-shape accounting
========================

The engine's one jitted step takes the parameters as an argument, so the
jit cache keys on (parameter structure, token shape).  Draft parameters
only ever run the ``(slots, 1)`` shape; the exact parameters only ever run
``(slots, chunk)`` — under speculation even decode-only turns go
chunk-shaped (as ``n_valid = 1`` rows), never thin.  Exactly two cache
entries per KV layout, the same bound as non-speculative serving.

This module is pure host-side planning/acceptance logic; the engine owns
dispatch and the scheduler owns batch construction
(``SlotScheduler.draft_batch`` / ``verify_batch``).
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.serving.request import Request, RequestState

__all__ = ["SpecRow", "SpecRound", "plan_round", "draft_inputs",
           "record_drafts", "accept"]


@dataclasses.dataclass
class SpecRow:
    """One decoding slot's state across a single speculative round."""

    req: Request
    #: draft tokens this round (>= 1; capped by remaining budget and chunk)
    k_eff: int
    #: greedy draft tokens d_1..d_k_eff, filled during the draft phase
    drafts: list[int] = dataclasses.field(default_factory=list)
    #: longest agreeing draft prefix (set at verify; the acceptance metric
    #: counts THIS, independent of stop-condition truncation)
    accepted: int = 0
    #: tokens actually emitted (accepted prefix + correction, truncated at
    #: the first stop condition); the final cursor is base + emitted
    emitted: int = 0


@dataclasses.dataclass
class SpecRound:
    """One engine iteration's speculative plan.

    ``prefilling`` rows advance their prompt chunk inside the verify call;
    ``spec_rows`` draft then verify; ``plain`` rows (no draft budget this
    round) decode one token as ``n_valid = 1`` riders on the verify call —
    keeping every exact-parameter dispatch chunk-shaped."""

    prefilling: list[Request]
    spec_rows: list[SpecRow]
    plain: list[Request]

    @property
    def max_k(self) -> int:
        return max((row.k_eff for row in self.spec_rows), default=0)


def plan_round(active: dict[int, Request], k: int,
               prefill_chunk: int) -> SpecRound | None:
    """Partition the active requests into this round's roles.

    ``k_eff = min(k, budget - 1, chunk - 1)``: the budget cap makes the
    round's maximum emission count (``k_eff + 1``) fit the remaining
    generation allowance — which is also what keeps draft-phase cursors
    ``<= max_len - 1`` and verify writes inside the paged layout's
    reserved blocks; the chunk cap fits ``[x, d_1..d_k]`` in one verify
    row.  Returns None when nothing is runnable."""
    prefilling = [r for r in active.values()
                  if r.state == RequestState.PREFILL]
    decoding = [r for r in active.values()
                if r.state == RequestState.DECODE]
    if not prefilling and not decoding:
        return None
    spec_rows: list[SpecRow] = []
    plain: list[Request] = []
    for r in decoding:
        budget = r.max_new_tokens - len(r.generated)
        k_eff = min(k, budget - 1, prefill_chunk - 1)
        if k_eff >= 1:
            spec_rows.append(SpecRow(r, k_eff))
        else:
            plain.append(r)
    return SpecRound(prefilling, spec_rows, plain)


def draft_inputs(rnd: SpecRound, slots: int,
                 i: int) -> tuple[np.ndarray, np.ndarray]:
    """Token/n_valid arrays for draft call ``i`` (thin ``(slots, 1)``).

    Each participating row feeds its previous greedy output: the request's
    last emitted token on call 0, then its own latest draft.  Rows done
    drafting (and prefill/plain/idle slots) are ``n_valid = 0`` padding —
    their cursors do not move and their writes are masked."""
    tokens = np.zeros((slots, 1), np.int32)
    n_valid = np.zeros((slots,), np.int32)
    for row in rnd.spec_rows:
        if i < row.k_eff:
            r = row.req
            tokens[r.slot, 0] = row.drafts[-1] if row.drafts else r.generated[-1]
            n_valid[r.slot] = 1
    return tokens, n_valid


def record_drafts(rnd: SpecRound, i: int, toks: np.ndarray) -> None:
    """Fold draft call ``i``'s per-slot argmax into each active row."""
    for row in rnd.spec_rows:
        if i < row.k_eff:
            row.drafts.append(int(toks[row.req.slot]))


def accept(row: SpecRow, verifier_row: np.ndarray) -> list[int]:
    """Longest-agreeing-prefix acceptance for one verify row.

    ``verifier_row[i]`` is the exact model's greedy token after verify
    input ``i`` (inputs are ``[x, d_1 .. d_k]``), i.e. ``v_{i+1}``.
    Returns the emission candidates ``v_1 .. v_{j+1}`` — the ``j``
    accepted drafts (``v_i == d_i`` for ``i <= j``) plus the verifier's
    correction token.  Every candidate is an exact-model output over
    accepted-exact history, so emitting them preserves bit-identity with
    sequential exact decode; the caller truncates at the first stop
    condition and sets ``row.emitted``."""
    k = row.k_eff
    v = [int(t) for t in verifier_row[:k + 1]]
    j = 0
    while j < k and v[j] == row.drafts[j]:
        j += 1
    row.accepted = j
    return v[:j + 1]
