"""The continuous-batching serving engine.

:class:`ServingEngine` ties the pieces together: submit() runs admission
control (with priority eviction from a full queue) and enqueues; step()
admits into free slots (``kv_layout="paged"`` additionally requires every
block a request can need to be reservable, and may skip cached shared-
prefix prefill entirely), asks the scheduler for one fixed-shape batch —
chunk-shaped with mixed prefill+decode rows when both kinds pend
(``EngineConfig.mixed_batches``), thin ``(slots, 1)`` otherwise — runs the
jitted slot step, and advances every participating request through one
unified per-row postprocess (streaming tokens to callbacks as they decode).

The same engine serves float, exact-int8, and approximate+CV packed
parameters — numerics live entirely in the parameter representation
(``repro.launch.serve.build_serving_params``), not in the engine.  The
engine records which NumericsSpec produced its parameters (``numerics=``,
normally the spec's name) and surfaces it through the metrics snapshot so
a fleet's per-engine numerics are auditable from monitoring alone.

Generation is greedy (argmax), matching the sequential
``prefill``/``decode_step`` baseline token for token — the equivalence
contract tested by tests/test_serving_engine.py.

With ``EngineConfig.speculative_k > 0`` and a second, APPROXIMATE
parameter set (``draft_params=``) the engine runs self-verifying
speculative decode (``repro.serving.speculative``): the approximate
parameters draft k greedy tokens per slot on the thin step, one
chunk-shaped exact call verifies them all, and only verifier tokens are
emitted — same bit-exact contract, fewer exact dispatches per token.
"""

from __future__ import annotations

import itertools
import time
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig, EngineConfig
from repro.models import ModelApi, build_model
from repro.models.lm import rollback_slots
from repro.serving.kv_pool import SlotPool
from repro.serving.metrics import EngineMetrics
from repro.serving.request import (AdmissionController, Request, RequestQueue,
                                   RequestState)
from repro.serving.scheduler import ScheduledBatch, SlotScheduler
from repro.serving import speculative, telemetry
from repro.serving.telemetry import SpanTracer

#: Compiler options of every serving step.  XLA may otherwise skip a
#: bfloat16 rounding the program asks for where it fuses the producer into
#: the consumer (its "excess precision" rule), and what it fuses depends on
#: the call's shape and on the ops around it.  With every rounding kept, a
#: row's numbers do not depend on whether a projection runs as an XLA dot
#: or as a Pallas kernel (on a TPU v5e the two programs otherwise diverge
#: from the first residual add), nor on how the surrounding ops fuse.
STEP_COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


def _has_blocked_packs(params) -> bool:
    """True iff any packed leaf ships the offline-blocked Pallas layout
    (the only path the decode-specialized block picker applies to)."""
    from repro.core.approx_linear import QuantizedDense, QuantizedDenseGroup

    found = False

    def walk(node):
        nonlocal found
        if found:
            return
        if isinstance(node, (QuantizedDense, QuantizedDenseGroup)):
            found = found or node.blocked is not None
        elif isinstance(node, dict):
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(params)
    return found


def power_profile_from_params(params, n_array: int = 64) -> dict:
    """Per-layer modeled MAC cost/saving profile of a packed parameter
    tree: ``{path: {mac_per_token, saving_pct}}``.

    ``mac_per_token`` is the layer's MAC count per served token (the
    product of its weight shape — leading scan/stack dims included, so a
    stacked layer counts every member).  ``saving_pct`` is the cost
    model's modeled array-power saving for the layer's policy (0 for
    exact/float layers).  Only linear layers are profiled — they are
    where the approximate multipliers live, and the quantity the paper's
    power model prices.  This is the ``PackPlan`` x ``cost_model`` join
    evaluated on the LIVE pack, so a governor hot-swap re-derives it from
    whatever is actually serving (see ``EngineMetrics.set_power_profile``).
    """
    from repro.core.approx_linear import (QuantizedDense,
                                          QuantizedDenseGroup,
                                          is_linear_params)
    from repro.core.cost_model import power_saving

    prof: dict[str, dict] = {}

    def add(path, shape, policy):
        saving = (power_saving(policy.mode, policy.m, n_array)
                  if policy is not None and policy.is_approx else 0.0)
        prof[path] = {"mac_per_token": float(np.prod(shape)),
                      "saving_pct": round(float(saving), 3)}

    def walk(node, path):
        if isinstance(node, (QuantizedDense, QuantizedDenseGroup)):
            add(path, node.pack.w_q.shape, node.policy)
        elif isinstance(node, dict):
            if is_linear_params(node):
                add(path, node["w"].shape, None)
                return
            for k, v in node.items():
                walk(v, f"{path}/{k}" if path else str(k))
        elif isinstance(node, (list, tuple)):
            for i, v in enumerate(node):
                walk(v, f"{path}/{i}" if path else str(i))

    walk(params, "")
    return prof


class ServingEngine:
    def __init__(self, cfg: ArchConfig, params, ecfg: EngineConfig = EngineConfig(),
                 mesh=None, api: ModelApi | None = None,
                 numerics: str | None = None,
                 draft_params=None, draft_numerics: str | None = None,
                 governor=None, pack_fn: Callable | None = None,
                 fault_injector=None, exact_params=None,
                 engine_id: str | None = None,
                 shadow_params=None,
                 shadow_numerics: str | None = None) -> None:
        self.cfg = cfg
        self.ecfg = ecfg
        self.params = params
        self.api = api or build_model(cfg)
        self.numerics = numerics  # active NumericsSpec name (None = unknown)
        #: stable identity for traces and fleet routing; defaults to the
        #: numerics label (the pre-fleet behavior, where one engine WAS
        #: the deployment and its spec named it)
        self.engine_id = engine_id or numerics or "engine"
        # speculative decode: ``params`` verifies (and serves prefill),
        # ``draft_params`` — the same weights packed under an approximate
        # spec — proposes.  Kept fully optional: without speculative_k the
        # engine never touches them.
        self.draft_params = draft_params
        self.draft_numerics = draft_numerics
        self._spec_k = int(ecfg.speculative_k)
        if self._spec_k:
            if draft_params is None:
                raise ValueError(
                    "speculative_k > 0 needs draft_params: the approximate-"
                    "spec packed parameters that draft for this engine "
                    "(same weights, different numerics — see "
                    "repro.launch.serve.build_serving_params)")
            if cfg.rwkv:
                # rollback is a cursor move over position-indexed K/V;
                # recurrent per-slot state cannot rewind a rejected draft
                raise NotImplementedError(
                    f"{cfg.name}: speculative decode needs a position-"
                    "indexed KV cache to roll back rejected drafts "
                    "(recurrent RWKV state cannot rewind)")
        if ecfg.kv_layout == "paged":
            from repro.serving.paged import PagedKVPool

            self.pool = PagedKVPool(self.api, ecfg)
        elif ecfg.kv_layout == "contiguous":
            self.pool = SlotPool(self.api, ecfg.slots, ecfg.max_len,
                                 ecfg.cache_dtype)
        else:
            raise ValueError(f"unknown kv_layout {ecfg.kv_layout!r}; "
                             "valid choices: ['contiguous', 'paged']")
        self._paged = ecfg.kv_layout == "paged"
        # cumulative pool counters at the start of the metrics window
        self._block_baseline = (self.pool.block_stats() if self._paged
                                else None)
        self.queue = RequestQueue()
        # paged: admission also screens out jobs whose worst-case block
        # need exceeds the whole pool (they could never be placed and
        # would wedge the FIFO head in an eternal capacity stall)
        self.admission = AdmissionController(
            ecfg.max_queue, ecfg.max_len, ecfg.prefill_chunk,
            kv_block_size=ecfg.kv_block_size if self._paged else None,
            kv_blocks=self.pool.blocks_total if self._paged else None)
        self.scheduler = SlotScheduler(ecfg.slots, ecfg.prefill_chunk,
                                       ecfg.interleave, ecfg.mixed_batches)
        # decode steps are (slots, 1) token blocks: a slot count within the
        # kernel block picker's decode window means every continuous-decode
        # iteration runs the thin-M, single-K-step specialized tiles — but
        # only the Pallas blocked packs go through that picker, so the flag
        # is gated on the served parameters actually carrying blocked layouts
        from repro.kernels.ops import DECODE_M_MAX

        self.metrics = EngineMetrics(
            numerics=numerics,
            kv_layout=ecfg.kv_layout,
            decode_specialized=(ecfg.slots <= DECODE_M_MAX
                                and _has_blocked_packs(params)),
            window_s=ecfg.metrics_window_s,
            speculative_k=self._spec_k,
            draft_numerics=draft_numerics if self._spec_k else None)
        # request-span tracing: a bounded per-engine ring of typed events,
        # recorded at points the engine already touches each request
        self.tracer = (SpanTracer(capacity=ecfg.trace_buffer,
                                  engine=self.engine_id)
                       if ecfg.trace else None)
        self._bridge_window_samples()
        # approximation-error probe: every N steps, one scheduled row is
        # re-run eagerly through the exact-int8 path (repro.quant.error_probe)
        self._probe = None
        self._steps = 0
        if ecfg.error_probe_every > 0:
            from repro.quant.error_probe import ErrorProbe

            self._probe = ErrorProbe(self.api.decode_slots, mesh=mesh,
                                     paged=self._paged)
        # -- robustness layer (repro.serving.governor / repro.quant.faults) --
        # ``governor``: a NumericsGovernor walking the degradation ladder on
        # SLO breaches; ``pack_fn(spec_or_none) -> params`` builds the pack
        # for a rung on first use (cached per rung name).  ``fault_injector``
        # corrupts deterministically for testing; ``exact_params`` (optional)
        # is the pack quarantine replays run on (defaults to the live pack —
        # correct when the live pack IS exact, e.g. int8 serving).
        self.governor = governor
        self._pack_fn = pack_fn
        self._injector = fault_injector
        self._exact_params = exact_params
        self._detect = fault_injector is not None or ecfg.detect_faults
        self._rung_packs: dict = {}
        #: structural record of quarantine replays: {rid, slot, step, token}
        self.quarantine_log: list[dict] = []
        if governor is not None:
            if pack_fn is None:
                raise ValueError(
                    "a governor needs pack_fn: called with a rung's "
                    "NumericsSpec (or None for float) to build the pack it "
                    "hot-swaps in — see repro.launch.serve for the "
                    "build_serving_params closure")
            if ecfg.error_probe_every <= 0:
                raise ValueError(
                    "the governor consumes the error probe; set "
                    "EngineConfig.error_probe_every > 0")
            if self._spec_k:
                raise ValueError(
                    "governor + speculative decode is unsupported: "
                    "speculation already pins every emitted token to the "
                    "exact pack, so there is no approximate emission for "
                    "an SLO to govern")
            # the live params ARE the starting rung's pack
            self._rung_packs[governor.rung.name] = params
        if fault_injector is not None and self._spec_k:
            raise ValueError(
                "fault injection targets the plain serving path; the "
                "speculative path's emissions are exact-verified already")
        # -- A/B shadow serving (repro.serving.shadow) -----------------------
        # a sampled fraction of FINISHED requests replays teacher-forced
        # through a second pack on this engine's ModelApi; the replay
        # happens at finish time inside step() and records a "shadow" span
        self._shadow = None
        self._finish_count = 0
        if ecfg.shadow_fraction > 0:
            if shadow_params is None:
                raise ValueError(
                    "shadow_fraction > 0 needs shadow_params: the second "
                    "NumericsSpec pack sampled requests replay through "
                    "(same weights, different numerics)")
            if self._spec_k:
                raise ValueError(
                    "shadow serving + speculative decode is unsupported: "
                    "the draft pack already occupies the second-pack slot")
            if governor is not None:
                raise ValueError(
                    "shadow serving + governor is unsupported: a mid-run "
                    "hot-swap would mix regimes inside one A/B verdict")
            from repro.serving.shadow import ShadowRunner

            self._shadow = ShadowRunner(
                self.api, ecfg, params, shadow_params,
                primary_label=numerics or "primary",
                shadow_label=shadow_numerics or "shadow", mesh=mesh)
            self.metrics.shadow_numerics = self._shadow.shadow_label
        # modeled power attribution: profile the live pack per numerics
        # label (cached — a governor escalate/relax cycle profiles each
        # rung once) and register it with the metrics joiner
        self._power_profiles: dict = {}
        self._register_power_profile()
        self.active: dict[int, Request] = {}
        self._rid = itertools.count()
        decode_slots = self.api.decode_slots
        # the step donates the pooled cache, so its output cache takes the
        # input's buffers and the layer scan writes a step's new columns in
        # place — unless the engine holds the pre-step cache across the
        # step: the error probe re-runs a row from it.  The shadow runner
        # replays through a private pool and a jit of its own.
        self._donate = self._probe is None
        #: whether a step updates the cache in place: donated, and on the
        #: contiguous layout (the paged step gathers and scatters blocks)
        self.kv_in_place = self._donate and not self._paged

        #: MoE models' steps also return the MoE layers' counts, which the
        #: engine reads after its token fetch (an ``engine.moe`` span)
        self._moe = cfg.mlp == "moe"
        self._moe_counts = None
        kw = {"moe_counts": True} if self._moe else {}

        def step_fn(name: str):
            if self._paged:
                def fn(p, t, c, nv, bt):
                    return decode_slots(p, t, c, nv, mesh=mesh,
                                        block_tables=bt, **kw)
            else:
                def fn(p, t, c, nv):
                    return decode_slots(p, t, c, nv, mesh=mesh, **kw)

            fn.__name__ = fn.__qualname__ = name
            return jax.jit(fn, compiler_options=STEP_COMPILER_OPTIONS,
                           donate_argnums=(2,) if self._donate else ())

        # one jitted callable per shape, each compiled once: (slots, 1) and
        # (slots, chunk), so a device trace names the step that ran
        # (jit_engine_decode_step, jit_engine_chunk_step).  The paged layout
        # adds the fixed-shape block-table argument — its CONTENT changes
        # per admission, its shape never, so the invariant holds per layout.
        self._step_fns = {"decode": step_fn("engine_decode_step"),
                          "chunk": step_fn("engine_chunk_step")}

    def _bridge_window_samples(self) -> None:
        """Forward windowed metrics samples into the span trace as Chrome
        counter events (Perfetto renders them as time-series tracks)."""
        if self.tracer is not None and self.metrics.window_s > 0:
            # the sample's own "t" (wall-clock window stamp) must not shadow
            # record()'s monotonic t parameter — keep it as arg "window_t"
            self.metrics.on_window_sample = (
                lambda s: self.tracer.record(
                    "metrics_window",
                    **{("window_t" if k == "t" else k): v
                       for k, v in s.items()}))

    # -- submission ----------------------------------------------------------

    def submit(self, prompt, max_new_tokens: int, priority: int = 0,
               eos_id: int | None = None,
               on_token: Callable | None = None,
               deadline_ms: float | None = None) -> Request:
        """Admission-checked enqueue; returns the Request (maybe REJECTED).

        A request returned as QUEUED can still become REJECTED later: a
        full queue evicts its worst member when a strictly-higher-priority
        request arrives.  Callers polling a single Request must treat
        ``state == REJECTED`` as terminal alongside ``finished``."""
        req = Request(rid=next(self._rid), prompt=[int(t) for t in prompt],
                      max_new_tokens=int(max_new_tokens), priority=priority,
                      eos_id=eos_id, on_token=on_token,
                      deadline_ms=deadline_ms)
        self.metrics.submitted += 1
        tr = self.tracer
        ok, reason, evicted = self.admission.admit(self.queue, req)
        if not ok:
            req.state = RequestState.REJECTED
            req.reject_reason = reason
            self.metrics.rejected += 1
            if tr is not None:
                tr.record("rejected", rid=req.rid, reason=reason)
            return req
        if evicted is not None:
            # queue was full of strictly lower-priority work: the worst
            # queued request is re-rejected to make room for this one
            evicted.state = RequestState.REJECTED
            evicted.reject_reason = (f"evicted from full queue by "
                                     f"higher-priority request {req.rid}")
            self.metrics.rejected += 1
            self.metrics.evicted += 1
            if tr is not None:
                tr.record("evicted", rid=evicted.rid, by=req.rid)
        self.queue.push(req)
        if tr is not None:
            tr.record("queued", rid=req.rid, t=req.t_queued_mono,
                      prompt_len=req.prompt_len, priority=req.priority)
        return req

    # -- engine loop ---------------------------------------------------------

    @property
    def idle(self) -> bool:
        return not self.active and not len(self.queue)

    def step(self) -> list[Request]:
        """One engine iteration; returns requests that finished in it
        (including queued requests evicted by an expired deadline — they
        are terminal without ever touching a slot).

        The iteration is one ``engine.step`` span with its phases
        (``schedule``, ``dispatch``, ``fetch``, ``emit``, ``account``) as
        children; see :mod:`repro.serving.telemetry`."""
        with telemetry.spans(self.tracer)("step", step=self._steps) as st:
            return self._step(st)

    def _step(self, st: telemetry.Span) -> list[Request]:
        tr = self.tracer
        span = telemetry.spans(tr)
        with span("schedule", queued=len(self.queue)) as sc:
            expired = self.scheduler.purge_expired(self.queue, self.metrics,
                                                   tracer=tr)
            admitted = self.scheduler.admit(self.queue, self.pool,
                                            self.active, self.metrics,
                                            tracer=tr)
            for r in admitted:
                if r.prefix_hit_tokens:
                    self.metrics.prefix_hits += 1
                    self.metrics.prefix_hit_tokens += r.prefix_hit_tokens
                    if tr is not None:
                        tr.record("prefix_hit", rid=r.rid,
                                  hit_tokens=r.prefix_hit_tokens)
            if self._spec_k:
                # every turn goes through the speculative round — including
                # turns with zero spec rows — so plain decode rows always
                # ride chunk-shaped exact calls and the exact parameters
                # never meet the thin shape (two compiled shapes total, same
                # as plain serving: draft structure x thin + exact
                # structure x chunk)
                rnd = speculative.plan_round(self.active, self._spec_k,
                                             self.ecfg.prefill_chunk)
            else:
                batch = self.scheduler.next_batch(self.active)
            sc.set(admitted=len(admitted))
        if self._spec_k:
            if rnd is None:
                return expired
            return expired + self._speculative_step(rnd, st)
        if batch is None:
            return expired
        if st.recording:
            st.set(shape=_shape(batch), **_row_counts(batch))
        # arm the throughput clock BEFORE the dispatch: warmup between
        # construction and the first served batch stays excluded, but the
        # first measured step's own wall time is inside the window
        self.metrics.start_clock()
        with span("dispatch", in_place=self.kv_in_place) as dsp:
            tables = None
            if self._paged:
                # copy-on-write barrier: every block this batch writes must
                # be uniquely owned before the jitted step sees the tables
                cow0 = self.pool.cow_copies if tr is not None else 0
                for slot, nv in enumerate(batch.n_valid):
                    self.pool.ensure_writable(slot, int(nv))
                self.pool.flush_copies()
                if tr is not None and self.pool.cow_copies > cow0:
                    tr.record("cow_copy", copies=self.pool.cow_copies - cow0)
                tables = self.pool.block_tables_array()
            cache_before = self.pool.cache
            logits, new_cache = self._dispatch(self.params, batch, tables)
            self.pool.update(new_cache)
            if self._paged:
                self.pool.advance(batch.n_valid)
        # fault injection (step surface): corrupt chosen rows' logits on
        # the host, modeling a transient corruption of the step's output;
        # the detector below must catch every one before emission
        if (self._injector is not None
                and self._injector.spec.surface == "step"
                and self._injector.fires(self._steps)):
            live = [r.slot for r in batch.rows
                    if batch.n_valid[r.slot] > 0]
            bad_rows = self._injector.plan_rows(self._steps, live)
            if bad_rows:
                logits = self._injector.corrupt_logits(self._steps, logits,
                                                       bad_rows)
                self.metrics.faults_injected += len(bad_rows)
        if self._detect:
            # profiler only: the ring records one quarantine event per row
            with telemetry.span("quarantine"):
                pp_batch, q_finished, q_emitted, q_prompt = self._quarantine(
                    batch, logits, tables)
        else:
            pp_batch, q_finished, q_emitted, q_prompt = batch, [], 0, 0
        finished, emitted, prompt_toks = self._postprocess(pp_batch, logits)
        self._record_moe()
        finished += q_finished
        emitted += q_emitted
        prompt_toks += q_prompt
        with span("account"):
            if tr is not None:
                # a row's span: from its dispatch to its accounting
                dur = telemetry.clock() - dsp.t
                for r, kind in zip(batch.rows, batch.row_kinds):
                    tr.record("prefill_chunk" if kind == "prefill"
                              else "decode_step", rid=r.rid, t=dsp.t, dur=dur,
                              slot=r.slot, n_valid=int(batch.n_valid[r.slot]))
                for r in finished:
                    tr.record("finished", rid=r.rid, reason=r.finish_reason,
                              generated=len(r.generated))
            self.metrics.record_step(
                batch.kind, self.pool.occupancy, len(self.queue),
                prompt_tokens=prompt_toks, generated_tokens=emitted,
                block_stats=(self._windowed_block_stats() if self._paged
                             else None))
        self._steps += 1
        if (self._probe is not None
                and self._steps % self.ecfg.error_probe_every == 0):
            self._run_probe(batch, cache_before, tables)
        if self._shadow is not None and finished:
            self._run_shadow(finished)
        return expired + finished

    # -- A/B shadow serving (repro.serving.shadow) ---------------------------

    def _run_shadow(self, finished: list[Request]) -> None:
        """Replay sampled finished requests through the shadow pack.

        Sampling is deterministic (every Nth finished request with
        generated tokens), the replay is teacher-forced along the
        PRIMARY's emitted tokens, and each replay records a ``shadow``
        span whose duration is the replay's wall time — so stall
        attribution prices shadow cost like probe cost."""
        for r in finished:
            if not r.generated:
                continue
            self._finish_count += 1
            if not self._shadow.wants(self._finish_count):
                continue
            with telemetry.spans(self.tracer)("shadow", rid=r.rid) as sp:
                rec = self._shadow.replay(r.prompt, r.generated)
                sp.set(tokens=rec["tokens"], matches=rec["matches"],
                       logits_err_var=rec["logits_err"]["var"],
                       logits_err_max_abs=rec["logits_err"]["max_abs"])
            self.metrics.record_shadow(rec)

    def shadow_verdict(self) -> dict | None:
        """The accumulated accuracy-vs-power A/B verdict (None when no
        shadow is configured or nothing was sampled yet)."""
        return self._shadow.verdict() if self._shadow is not None else None

    # -- fault detection & quarantine (repro.quant.faults) -------------------

    def _quarantine(self, batch: ScheduledBatch, logits,
                    tables) -> tuple[ScheduledBatch, list[Request], int, int]:
        """Detect corrupted rows in this step's logits; quarantine them.

        Detection reads each live row's consumed column (``n_valid - 1``)
        and flags non-finite or divergent values
        (:func:`repro.quant.faults.suspect_rows`).  A flagged row's KV
        cursor rolls back to its pre-step value (``set_lengths`` — a pure
        cursor move on both layouts, PR 7's rollback primitive) and the
        row REPLAYS through the exact pack with the injector never
        consulted, so the corrupted logits are discarded before any token
        is emitted.  Returns the cleaned batch (flagged rows removed) and
        the replay's ``(finished, emitted, prompt_tokens)``.
        """
        from repro.quant import faults

        nv = np.asarray(batch.n_valid)
        live = [(r, k) for r, k in zip(batch.rows, batch.row_kinds)
                if nv[r.slot] > 0]
        if not live:
            return batch, [], 0, 0
        lg = np.asarray(logits)
        cols = np.maximum(nv - 1, 0)
        picked = lg[np.arange(lg.shape[0]), cols]  # (slots, vocab)
        slots = np.array([r.slot for r, _ in live])
        mask = faults.suspect_rows(picked[slots])
        if not mask.any():
            return batch, [], 0, 0
        bad = [live[i] for i in np.nonzero(mask)[0]]
        bad_slots = {r.slot for r, _ in bad}
        tr = self.tracer
        self.metrics.faults_detected += len(bad)
        if tr is not None:
            for r, _ in bad:
                tr.record("fault_detected", rid=r.rid, slot=r.slot,
                          step=self._steps)
        if self.governor is not None:
            # a detected fault is an unbounded-variance observation: the
            # governor escalates immediately, no window arithmetic
            self._apply_decision(self.governor.note_fault())
        # roll the flagged slots' cursors back to their pre-step values
        # (post-step length = pre-step + n_valid on both layouts)
        cur = np.array(self.pool.lengths())  # lengths() can be a read-only
        for r, _ in bad:                     # view of the device array
            cur[r.slot] -= int(nv[r.slot])
        self.pool.set_lengths(cur)
        # replay ONLY the flagged rows on the exact pack; same batch shape,
        # so the jit cache grows by at most one (params structure) entry
        rep_nv = np.zeros_like(nv)
        for r, _ in bad:
            rep_nv[r.slot] = nv[r.slot]
        rep_batch = ScheduledBatch(batch.kind, batch.tokens, rep_nv,
                                   [r for r, _ in bad], [k for _, k in bad])
        rep_params = (self._exact_params if self._exact_params is not None
                      else self.params)
        rep_logits, rep_cache = self._dispatch(rep_params, rep_batch, tables)
        self.pool.update(rep_cache)
        if self._paged:
            self.pool.advance(rep_nv)
        self.metrics.quarantines += len(bad)
        self.metrics.quarantine_replays += len(bad)
        finished, emitted, prompt_toks = self._postprocess(rep_batch,
                                                           rep_logits)
        for r, _ in bad:
            tok = r.generated[-1] if r.generated else None
            self.quarantine_log.append({"rid": r.rid, "slot": r.slot,
                                        "step": self._steps, "token": tok})
            if tr is not None:
                tr.record("quarantine", rid=r.rid, slot=r.slot,
                          step=self._steps, replayed=int(rep_nv[r.slot]))
        clean_nv = np.array(nv, copy=True)
        clean_nv[list(bad_slots)] = 0
        clean = ScheduledBatch(
            batch.kind, batch.tokens, clean_nv,
            [r for r in batch.rows if r.slot not in bad_slots],
            [k for r, k in zip(batch.rows, batch.row_kinds)
             if r.slot not in bad_slots])
        return clean, finished, emitted, prompt_toks

    def _dispatch(self, params, batch: ScheduledBatch, tables):
        """Run the jitted slot step under the given parameter set.

        The parameters are a traced argument, so draft and exact packs
        share the callables and each one's jit cache keys on the parameter
        structure."""
        fn = self._step_fns[_shape(batch)]
        n_valid = jnp.asarray(batch.n_valid)
        args = (params, jnp.asarray(batch.tokens), self.pool.cache, n_valid)
        if self._paged:
            args += (jnp.asarray(tables),)
        logits, cache, *moe = fn(*args)
        self._moe_counts = moe[0] if moe else None
        if self._donate:
            # the step consumed the pool's buffers.  Until the caller stores
            # the result, the pool holds it at the pre-step cursors: for
            # position-indexed K/V that is the pre-step cache, as nothing
            # past a cursor is attended before it is written (see
            # repro.models.lm.rollback_slots)
            self.pool.update(rollback_slots(cache, cache["lengths"] - n_valid))
            if self.kv_in_place:
                self.metrics.kv_in_place_steps += 1
        return logits, cache

    def _record_moe(self) -> None:
        """After the token fetch: the last step's MoE counts as the
        arguments ``pairs`` and ``groups_hit`` of a zero-length
        ``engine.moe`` span, read only while a ring or a profiler trace
        keeps them."""
        if self._moe_counts is None or not (
                self.tracer is not None
                or jax.profiler.TraceAnnotation.is_enabled()):
            return
        pairs, groups = (int(v) for v in np.asarray(self._moe_counts))
        telemetry.instant(self.tracer, "moe", pairs=pairs, groups_hit=groups)

    # -- speculative rounds (repro.serving.speculative) ----------------------

    def _speculative_step(self, rnd, st: telemetry.Span) -> list[Request]:
        """One draft-and-verify round.

        Draft: up to ``rnd.max_k`` thin calls with the APPROXIMATE
        parameters, each feeding the previous argmax; rollback to the
        pre-draft cursors (pure cursor move — the draft K/V is masked and
        then overwritten).  Verify: ONE chunk-shaped call with the exact
        parameters whose verify rows re-run ``[last-token, drafts]`` with
        ``n_valid = k_eff + 1``; prefill chunks and budget-exhausted
        decode rows ride the same call.  Emission takes each row's longest
        agreeing prefix plus the verifier's correction token — every
        emitted token is an exact-model output, so the stream stays
        bit-identical to plain exact decode — and the final cursors land
        on exactly the accepted history."""
        tr = self.tracer
        span = telemetry.spans(tr)
        self.metrics.start_clock()
        ch = self.ecfg.prefill_chunk
        tables = None
        if self._paged:
            # ONE copy-on-write barrier covers the whole round: prompt
            # chunks, draft writes [L, L+k) and verify writes [L, L+k] all
            # land in blocks made uniquely owned here, so the tables stay
            # valid across every dispatch below (rollback is a cursor move
            # — it never frees or remaps a block)
            with span("dispatch", call="cow"):
                cow0 = self.pool.cow_copies if tr is not None else 0
                for r in rnd.prefilling:
                    self.pool.ensure_writable(
                        r.slot, min(ch, r.prompt_len - r.prefilled))
                for row in rnd.spec_rows:
                    self.pool.ensure_writable(row.req.slot, row.k_eff + 1)
                for r in rnd.plain:
                    self.pool.ensure_writable(r.slot, 1)
                self.pool.flush_copies()
                if tr is not None and self.pool.cow_copies > cow0:
                    tr.record("cow_copy", copies=self.pool.cow_copies - cow0)
                tables = self.pool.block_tables_array()
        base = self.pool.lengths()

        # -- draft phase: thin calls, APPROXIMATE parameters ----------------
        max_k = rnd.max_k
        t_draft = telemetry.clock() if tr is not None else 0.0
        for i in range(max_k):
            db = self.scheduler.draft_batch(rnd, i)
            with span("dispatch", call="draft",
                      in_place=self.kv_in_place):
                logits, new_cache = self._dispatch(self.draft_params, db,
                                                   tables)
                self.pool.update(new_cache)
            with span("fetch", call="draft"):
                toks = np.asarray(jnp.argmax(logits[:, 0], axis=-1))
            speculative.record_drafts(rnd, i, toks)
        if max_k:
            # the draft K/V above each base cursor is approximate junk:
            # retreat the cursors (repro.models.lm.rollback_slots) and let
            # the verify call overwrite those positions with exact K/V
            self.pool.set_lengths(base)

        # -- verify phase: ONE chunk-shaped call, EXACT parameters ----------
        vb = self.scheduler.verify_batch(rnd)
        if st.recording:
            st.set(shape=_shape(vb), draft_calls=max_k, **_row_counts(vb))
        with span("dispatch", call="verify",
                  in_place=self.kv_in_place) as verify:
            cache_before = self.pool.cache
            logits, new_cache = self._dispatch(self.params, vb, tables)
            self.pool.update(new_cache)

        (finished, emitted, prompt_toks,
         drafted, accepted) = self._spec_postprocess(rnd, vb, logits)

        # final cursors: base + chunk (prefill rows), base + 1 (plain
        # decode rows), base + emitted (verify rows — the device advanced
        # k_eff + 1; rejected or stop-truncated positions roll back, their
        # stale exact K/V masked until overwritten next round).  This
        # replaces the plain path's pool.advance and keeps the paged host
        # mirror in sync; released slots re-zero their cursor on acquire.
        final = base.copy()
        for r, kind in zip(vb.rows, vb.row_kinds):
            if kind != "verify":
                final[r.slot] = base[r.slot] + int(vb.n_valid[r.slot])
        for row in rnd.spec_rows:
            final[row.req.slot] = base[row.req.slot] + row.emitted
        self.pool.set_lengths(final)

        with span("account"):
            if tr is not None:
                for r, kind in zip(vb.rows, vb.row_kinds):
                    if kind == "verify":
                        continue
                    tr.record("prefill_chunk" if kind == "prefill"
                              else "decode_step", rid=r.rid, t=verify.t,
                              dur=verify.dur, slot=r.slot,
                              n_valid=int(vb.n_valid[r.slot]))
                for row in rnd.spec_rows:
                    tr.record("draft", rid=row.req.rid, t=t_draft,
                              dur=verify.t - t_draft, slot=row.req.slot,
                              k=row.k_eff)
                    tr.record("verify", rid=row.req.rid, t=verify.t,
                              dur=verify.dur, slot=row.req.slot,
                              drafted=row.k_eff, accepted=row.accepted,
                              emitted=row.emitted)
                for r in finished:
                    tr.record("finished", rid=r.rid, reason=r.finish_reason,
                              generated=len(r.generated))
            self.metrics.record_step(
                "spec" if rnd.spec_rows
                else ("mixed" if rnd.plain else "prefill"),
                self.pool.occupancy, len(self.queue),
                prompt_tokens=prompt_toks, generated_tokens=emitted,
                block_stats=(self._windowed_block_stats() if self._paged
                             else None),
                drafted=drafted, accepted=accepted, draft_calls=max_k)
        self._steps += 1
        if (self._probe is not None
                and self._steps % self.ecfg.error_probe_every == 0):
            # the probe re-runs a verify-batch row against the exact path;
            # under speculation the serving params for that call ARE exact,
            # so it reports the (near-zero) noise floor — still useful as a
            # liveness check, documented in docs/serving.md
            self._run_probe(vb, cache_before, tables)
        return finished

    def _spec_postprocess(self, rnd, vb: ScheduledBatch,
                          logits) -> tuple[list[Request], int, int, int, int]:
        """Per-row advance for a speculative round's verify call.

        Prefill and plain-decode rows behave exactly as in
        :meth:`_postprocess`; verify rows run longest-agreeing-prefix
        acceptance and emit their candidates one at a time through the
        normal stop checks — eos/length can only fire on an EMITTED
        verifier token, never on a drafted-but-rejected one (a rejected
        draft that happens to equal ``eos_id`` must not finish the
        request).  Returns ``(finished, generated_tokens, prompt_tokens,
        drafted, accepted)``; the acceptance counters use the agreement
        length, independent of stop-condition truncation."""
        span = telemetry.spans(self.tracer)
        # verify rows consume up to k_eff + 1 columns each, so take the
        # argmax over the full (slots, C, V) block once; every row kind
        # then reads from the same host array
        with span("fetch"):
            toks = np.asarray(jnp.argmax(logits, axis=-1))
        with span("emit"):
            return self._spec_emit(rnd, vb, toks)

    def _spec_emit(self, rnd, vb: ScheduledBatch,
                   toks) -> tuple[list[Request], int, int, int, int]:
        finished: list[Request] = []
        emitted = prompt_toks = drafted = accepted = 0
        for r, kind in zip(vb.rows, vb.row_kinds):
            if kind == "prefill":
                n = int(vb.n_valid[r.slot])
                r.prefilled += n
                prompt_toks += n
                if self._paged:
                    self.pool.register_prefix(r.slot, r.prompt_len,
                                              r.prefilled)
                if r.prefilled < r.prompt_len:
                    if r.deadline_expired:
                        r.finish_reason = "deadline"
                        self.metrics.requests_deadline_expired += 1
                        finished.append(self._finish(r))
                    continue
                r.state = RequestState.DECODE
                self._emit_row(r, int(toks[r.slot, n - 1]), finished,
                               first=True)
                emitted += 1
            elif kind == "decode":
                self._emit_row(r, int(toks[r.slot, 0]), finished,
                               first=False)
                emitted += 1
        for row in rnd.spec_rows:
            r = row.req
            candidates = speculative.accept(row, toks[r.slot])
            drafted += row.k_eff
            accepted += row.accepted
            for tok in candidates:
                self._emit_row(r, tok, finished, first=False)
                row.emitted += 1
                emitted += 1
                if r.state == RequestState.FINISHED:
                    break  # accepted-but-past-stop candidates are dropped
        return finished, emitted, prompt_toks, drafted, accepted

    def _run_probe(self, batch: ScheduledBatch, cache_before,
                   tables) -> None:
        """One approximation-error probe against the batch the engine just
        served: the pre-step cache reference reproduces the row's forward
        (JAX arrays are immutable, so holding it is free).

        A dense-surface fault injector arms its thread-local hook around
        the probe's observe forward — a degraded MAC array corrupts what
        the probe measures, which is exactly how the governor sees it —
        and the report feeds the governor's running SLO estimate.  The
        ``probe`` span's duration is the eager probe forward's wall time:
        the decode gap it opens inside the step loop is then attributable
        to the probe instead of scheduler idle."""
        inj = self._injector
        with telemetry.spans(self.tracer)("probe") as sp:
            if inj is not None and inj.spec.surface == "dense":
                log0 = len(inj.log)
                with inj.armed(self._steps):
                    report = self._probe.run(self.params, batch.tokens,
                                             batch.n_valid, cache_before,
                                             block_tables=tables)
                self.metrics.faults_injected += len(inj.log) - log0
            else:
                report = self._probe.run(self.params, batch.tokens,
                                         batch.n_valid, cache_before,
                                         block_tables=tables)
            if report is None:
                sp.drop()
                return
            if sp.recording:
                lvars = {p: st["var"] for p, st in report["layers"].items()}
                extra = {}
                if lvars:
                    worst = max(lvars, key=lvars.get)
                    extra = {"max_layer_err_var": lvars[worst],
                             "worst_layer": worst}
                sp.set(rid=next((r.rid for r in batch.rows
                                 if r.slot == report["row"]), None),
                       logits_err_var=report["logits"]["var"],
                       logits_err_max_abs=report["logits"]["max_abs"],
                       mean_layer_err_var=(sum(lvars.values()) / len(lvars)
                                           if lvars else 0.0), **extra)
        self.metrics.record_probe(report)
        if self.governor is not None:
            self._apply_decision(self.governor.observe_probe(report))

    # -- governor execution (repro.serving.governor) -------------------------

    def _apply_decision(self, decision) -> None:
        """Execute one governor ladder move: hot-swap the live pack.

        Rung packs build lazily through ``pack_fn`` and cache per rung
        name, so an escalate/relax cycle packs each rung once.  The swap
        is a Python attribute assignment — the next dispatch traces the
        new parameter structure (one extra jit cache entry per rung, both
        batch shapes), every request's KV carries over untouched."""
        if decision is None:
            return
        rung = self.governor.rung
        pack = self._rung_packs.get(rung.name)
        if pack is None:
            pack = self._pack_fn(rung.spec)
            self._rung_packs[rung.name] = pack
        self.params = pack
        self.numerics = rung.name
        self.metrics.numerics = rung.name
        # the new rung's tokens attribute to ITS power profile from here on
        self._register_power_profile()
        self.metrics.governor_switches += 1
        if decision.action == "escalate":
            self.metrics.governor_escalations += 1
        else:
            self.metrics.governor_relaxes += 1
        if self.tracer is not None:
            self.tracer.record("governor_switch", step=self._steps,
                               **decision.to_dict())

    def _register_power_profile(self) -> None:
        """Profile the LIVE pack (cached per numerics label) and register
        it with the metrics power-attribution joiner."""
        label = self.numerics or "unknown"
        prof = self._power_profiles.get(label)
        if prof is None:
            prof = power_profile_from_params(self.params)
            self._power_profiles[label] = prof
        self.metrics.set_power_profile(label, prof)

    def _windowed_block_stats(self) -> dict:
        """Pool block stats with the cumulative counters rebased to the
        current metrics window, so one snapshot never mixes pool-lifetime
        numbers (cow_copies, prefix_evictions) with window-scoped ones."""
        stats = self.pool.block_stats()
        base = self._block_baseline
        return {**stats,
                "cow_copies": stats["cow_copies"] - base["cow_copies"],
                "prefix_evictions": (stats["prefix_evictions"]
                                     - base["prefix_evictions"])}

    def run(self, max_steps: int | None = None) -> list[Request]:
        """Drive until idle (or ``max_steps``); returns finished requests."""
        finished: list[Request] = []
        steps = 0
        while not self.idle:
            finished.extend(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return finished

    # -- replica handle ------------------------------------------------------
    # The surface a FleetRouter (repro.serving.fleet) drives a replica
    # through: submit / step / drain / load / snapshot / prefix sharing /
    # tracer.  Everything crossing it is plain Python data (token lists,
    # dicts, host numpy), so the same boundary could sit on a socket.

    def drain(self, max_steps: int | None = None) -> list[Request]:
        """Replica-handle verb for :meth:`run`: serve until idle."""
        return self.run(max_steps)

    def load(self) -> dict:
        """Routing-facing load signal: queue depth and slot pressure now,
        plus the replica's observed mean TTFT (None until one finishes).
        Cheap host bookkeeping only — the router polls this per submit."""
        backlog = self.scheduler.backlog(self.queue, self.active)
        ttfts = self.metrics.ttfts
        return {**backlog, "slots": self.ecfg.slots,
                "slots_free": self.pool.n_free,
                "ttft_mean_s": ttfts.mean if len(ttfts) else None}

    def snapshot(self) -> dict:
        """The metrics snapshot, as a plain dict (the handle boundary's
        observability payload; feeds ``EngineMetrics.merge``)."""
        return self.metrics.snapshot()

    def export_prefix(self) -> list[tuple[bytes, dict]]:
        """Export this replica's prefix-cache entries for adoption by a
        colder replica (paged layout; [] otherwise — nothing to share)."""
        if not self._paged:
            return []
        return self.pool.export_prefix_entries()

    def import_prefix(self, entries) -> int:
        """Adopt prefix entries exported by another replica; returns the
        number of blocks imported (0 on the contiguous layout)."""
        if not self._paged or not entries:
            return 0
        imported = self.pool.import_prefix_entries(entries)
        if imported:
            self.metrics.prefix_imports += imported
            if self.tracer is not None:
                self.tracer.record("prefix_import", blocks=imported)
        return imported

    def compile_count(self) -> int:
        """Number of shapes the jitted slot steps have compiled for."""
        return sum(fn._cache_size() for fn in self._step_fns.values())

    def reset_metrics(self) -> None:
        """Fresh counters (e.g. after warmup) without losing the numerics
        label the engine was built with.  The paged pool's cumulative
        counters (COW copies, prefix evictions, peak blocks) are rebased
        so the next snapshot covers one consistent window."""
        self.metrics = EngineMetrics(
            numerics=self.numerics,
            kv_layout=self.ecfg.kv_layout,
            decode_specialized=self.metrics.decode_specialized,
            window_s=self.ecfg.metrics_window_s,
            speculative_k=self._spec_k,
            draft_numerics=self.draft_numerics if self._spec_k else None,
            shadow_numerics=(self._shadow.shadow_label
                             if self._shadow is not None else None))
        self._bridge_window_samples()
        for label, prof in self._power_profiles.items():
            self.metrics.set_power_profile(label, prof)
        if self._paged:
            self.pool.reset_peak_blocks()
            self._block_baseline = self.pool.block_stats()

    # -- postprocessing ------------------------------------------------------

    def _postprocess(self, batch: ScheduledBatch,
                     logits) -> tuple[list[Request], int, int]:
        """Unified per-row advance for every batch kind.

        Each row's next token lives at logits column ``n_valid[slot] - 1``
        (a decode row's single column, or a prompt chunk's last real
        column).  Decode rows always emit; prefill rows emit only on the
        chunk that completes their prompt.  Returns
        ``(finished, generated_tokens, prompt_tokens)`` — per-row
        attribution, so mixed batches account both kinds at once.
        """
        emitting = any(
            kind == "decode"
            or r.prefilled + int(batch.n_valid[r.slot]) >= r.prompt_len
            for r, kind in zip(batch.rows, batch.row_kinds))
        span = telemetry.spans(self.tracer)
        toks = None
        if emitting:
            # gather each row's one needed column (n_valid-1) BEFORE the
            # argmax, then ship a (slots,) int array — not an argmax over
            # all C columns of (slots, C, V) in the hot serving loop
            with span("fetch"):
                cols = jnp.asarray(np.maximum(batch.n_valid - 1, 0))
                picked = jnp.take_along_axis(logits, cols[:, None, None],
                                             axis=1)
                toks = np.asarray(jnp.argmax(picked[:, 0], axis=-1))
        with span("emit"):
            return self._emit(batch, toks)

    def _emit(self, batch: ScheduledBatch,
              toks) -> tuple[list[Request], int, int]:
        finished, emitted, prompt_toks = [], 0, 0
        for r, kind in zip(batch.rows, batch.row_kinds):
            if kind == "prefill":
                n = int(batch.n_valid[r.slot])
                r.prefilled += n
                prompt_toks += n
                if self._paged:
                    # publish newly FULL prompt blocks as they fill, so
                    # concurrent requests share them before this one ends
                    self.pool.register_prefix(r.slot, r.prompt_len,
                                              r.prefilled)
                if r.prefilled < r.prompt_len:
                    if r.deadline_expired:
                        # blown mid-prompt: no first token can meet the
                        # SLO — stop before spending more prefill compute
                        r.finish_reason = "deadline"
                        self.metrics.requests_deadline_expired += 1
                        finished.append(self._finish(r))
                    continue
                # prompt complete: its last token's logits seed generation
                r.state = RequestState.DECODE
                self._emit_row(r, int(toks[r.slot]), finished, first=True)
            else:
                self._emit_row(r, int(toks[r.slot]), finished, first=False)
            emitted += 1
        return finished, emitted, prompt_toks

    def _emit_row(self, r: Request, tok: int, finished: list[Request],
                  first: bool) -> None:
        gap = r.emit(tok)
        if first:
            self.metrics.record_first_token(r)
        self.metrics.record_itl(gap)
        if self._done(r, tok):
            finished.append(self._finish(r))

    def _done(self, r: Request, tok: int) -> bool:
        """Stop check; records ``finish_reason`` at the moment it fires.
        Precedence: deadline > length > eos.  A blown deadline is the
        request's SLO verdict regardless of what the token says; within
        budget, the length stop takes precedence over an ``eos_id``
        coincidence on the budget's last step (as before)."""
        if r.deadline_expired:
            r.finish_reason = "deadline"
            self.metrics.requests_deadline_expired += 1
            return True
        if len(r.generated) >= r.max_new_tokens:
            r.finish_reason = "length"
            return True
        if r.eos_id is not None and tok == r.eos_id:
            r.finish_reason = "eos"
            return True
        return False

    def _finish(self, r: Request) -> Request:
        r.state = RequestState.FINISHED
        r.t_finish = time.time()
        self.pool.release(r.slot)
        del self.active[r.slot]
        self.metrics.record_finish(r)
        return r


def _shape(batch: ScheduledBatch) -> str:
    """Which compiled step a batch runs: ``decode`` (slots, 1) or ``chunk``
    (slots, prefill_chunk)."""
    return "decode" if batch.tokens.shape[1] == 1 else "chunk"


def _row_counts(batch: ScheduledBatch) -> dict:
    """Prompt tokens and decode tokens a batch processes (span args)."""
    nv = batch.n_valid
    decode = sum(int(nv[r.slot]) for r, kind in
                 zip(batch.rows, batch.row_kinds) if kind != "prefill")
    return {"prompt_rows": int(nv.sum()) - decode, "decode_rows": decode}
