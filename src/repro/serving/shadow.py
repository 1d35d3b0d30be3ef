"""A/B shadow serving: a second NumericsSpec pack mirrors live traffic.

The PR 2 NumericsSpec made packs declarative and the PR 7 speculative
path proved two packs can share one engine's jitted callable (parameters
are a traced argument, so the jit cache keys on parameter structure).
:class:`ShadowRunner` reuses that dual-pack machinery for *evaluation*
instead of drafting: a deterministic sample of FINISHED requests replays
teacher-forced — both packs forward the primary's emitted sequence in
``prefill_chunk``-shaped calls against a private slot cache — and the
runner diffs the two packs where it matters:

  * **tokens** — would the shadow pack have emitted the same argmax
    token at each generation position? (the same agreement measure as
    speculative acceptance, so numbers are comparable across both
    subsystems);
  * **logits** — elementwise logit-delta moments at generation
    positions, Chan-merged across replays (the serving-time analogue of
    the error probe's calibration-time residual);
  * **power** — each pack's MAC-weighted modeled array-power saving
    (:func:`repro.serving.engine.power_profile_from_params`).

:meth:`verdict` folds the three into an automated accuracy-vs-power
recommendation ("adopt-shadow" / "keep-primary" with the reason spelled
out) consumable by the ``serve`` CLI, ``trace_report``, and the
BENCH_serve.json shadow rows.

Replays are teacher-forced along the PRIMARY's tokens on purpose: both
packs see identical inputs at every position, so the diff isolates the
numerics instead of compounding trajectory divergence — and the replay
cost is ``ceil(len/chunk)`` chunk-shaped calls per pack, not one thin
call per token.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.serving.engine import STEP_COMPILER_OPTIONS
from repro.serving.kv_pool import SlotPool
from repro.serving.metrics import _merge_moments


class ShadowRunner:
    """Teacher-forced dual-pack replay + accuracy-vs-power verdict.

    ``min_token_match`` — token agreement at or above this adopts the
    shadow pack (if it also saves modeled power); below it the verdict
    is keep-primary on accuracy grounds.  ``slo_err_var`` — optional
    additional ceiling on the replayed logit-delta variance.
    """

    def __init__(self, api, ecfg, primary_params, shadow_params,
                 primary_label: str, shadow_label: str, mesh=None,
                 min_token_match: float = 0.9,
                 slo_err_var: float | None = None) -> None:
        if not 0 < ecfg.shadow_fraction <= 1:
            raise ValueError("shadow_fraction must be in (0, 1], got "
                             f"{ecfg.shadow_fraction}")
        if api.cfg.rwkv:
            raise NotImplementedError(
                f"{api.cfg.name}: shadow replay resets the slot cache by "
                "cursor; recurrent RWKV state has no cursor")
        self.primary_params = primary_params
        self.shadow_params = shadow_params
        self.primary_label = primary_label
        self.shadow_label = shadow_label
        self.fraction = float(ecfg.shadow_fraction)
        #: deterministic sampling: every Nth finished request replays
        self.every = max(1, round(1.0 / self.fraction))
        self.min_token_match = min_token_match
        self.slo_err_var = slo_err_var
        self.chunk = ecfg.prefill_chunk
        self.slots = ecfg.slots
        # a private slot cache (contiguous, whatever the engine serves
        # under): replays never touch the engine's pool, and the batch
        # shape matches the engine's chunk calls so the model sees
        # nothing new.  Reset between replays is the acquire semantics —
        # a cursor move; stale K/V beyond it is position-masked.
        self._pool = SlotPool(api, ecfg.slots, ecfg.max_len,
                              ecfg.cache_dtype)
        self._cache = self._pool.cache
        decode_slots = api.decode_slots
        # one jitted callable, one shape, BOTH packs: params are traced,
        # so primary and shadow structures share it (the speculative-
        # decode dual-pack mechanism, reused), compiled as the engine's
        # step is
        self._fn = jax.jit(
            lambda p, t, c, nv: decode_slots(p, t, c, nv, mesh=mesh),
            compiler_options=STEP_COMPILER_OPTIONS)
        # accumulated A/B state
        self.sampled = 0
        self.tokens = 0
        self.matches = 0
        self._logits: tuple[int, float, float] = (0, 0.0, 0.0)
        self._max_abs = 0.0
        # modeled pack power (MAC-weighted saving over the profile)
        self.primary_saving_pct = _pack_saving_pct(primary_params)
        self.shadow_saving_pct = _pack_saving_pct(shadow_params)

    # -- sampling ------------------------------------------------------------

    def wants(self, finish_index: int) -> bool:
        """Deterministic request sampling by finish order (1-based)."""
        return finish_index % self.every == 0

    # -- replay --------------------------------------------------------------

    def _forward(self, params, fed: list[int]) -> np.ndarray:
        """Teacher-forced logits for one token sequence, chunk by chunk.

        Row 0 of the (slots, chunk) batch carries the tokens; the other
        rows ride with ``n_valid = 0``.  Returns (len(fed), vocab)."""
        cache = {**self._cache,
                 "lengths": jnp.zeros_like(self._cache["lengths"])}
        outs = []
        for off in range(0, len(fed), self.chunk):
            part = fed[off:off + self.chunk]
            toks = np.zeros((self.slots, self.chunk), dtype=np.int32)
            toks[0, :len(part)] = part
            nv = np.zeros((self.slots,), dtype=np.int32)
            nv[0] = len(part)
            logits, cache = self._fn(params, jnp.asarray(toks), cache,
                                     jnp.asarray(nv))
            outs.append(np.asarray(logits[0, :len(part)], dtype=np.float32))
        self._cache = cache  # keep the allocations warm for the next replay
        return np.concatenate(outs, axis=0)

    def replay(self, prompt, generated) -> dict:
        """Replay one finished request through BOTH packs; returns the
        per-request record ``EngineMetrics.record_shadow`` consumes."""
        prompt = [int(t) for t in prompt]
        generated = [int(t) for t in generated]
        if not generated:
            raise ValueError("shadow replay needs generated tokens")
        plen = len(prompt)
        fed = prompt + generated[:-1]  # inputs; outputs predict fed[i+1]
        lg_p = self._forward(self.primary_params, fed)
        lg_s = self._forward(self.shadow_params, fed)
        # generation positions: fed index plen-1 predicts generated[0], ...
        gen_p = lg_p[plen - 1:]
        gen_s = lg_s[plen - 1:]
        pred_s = np.argmax(gen_s, axis=-1)
        matches = int((pred_s == np.asarray(generated)).sum())
        delta = (gen_s.astype(np.float64)
                 - gen_p.astype(np.float64)).ravel()
        rec = {
            "tokens": len(generated),
            "matches": matches,
            "logits_err": {"n": int(delta.size),
                           "mean": float(delta.mean()),
                           "var": float(delta.var()),
                           "max_abs": float(np.abs(delta).max())},
        }
        self.sampled += 1
        self.tokens += rec["tokens"]
        self.matches += matches
        le = rec["logits_err"]
        self._logits = _merge_moments(self._logits,
                                      (le["n"], le["mean"], le["var"]))
        self._max_abs = max(self._max_abs, le["max_abs"])
        return rec

    # -- verdict -------------------------------------------------------------

    def verdict(self) -> dict | None:
        """Automated accuracy-vs-power recommendation over everything
        sampled so far (None until a replay happened)."""
        if not self.sampled:
            return None
        match_rate = self.matches / self.tokens if self.tokens else 0.0
        _, _, err_var = self._logits
        power_delta = round(self.shadow_saving_pct
                            - self.primary_saving_pct, 2)
        accurate = match_rate >= self.min_token_match and (
            self.slo_err_var is None or err_var <= self.slo_err_var)
        if not accurate:
            decision = "keep-primary"
            if match_rate < self.min_token_match:
                reason = (f"token match {match_rate:.3f} below "
                          f"{self.min_token_match:g} threshold")
            else:
                reason = (f"logits err-var {err_var:.3g} above "
                          f"{self.slo_err_var:g} ceiling")
        elif power_delta > 0:
            decision = "adopt-shadow"
            reason = (f"token match {match_rate:.3f} >= "
                      f"{self.min_token_match:g} and modeled power saving "
                      f"+{power_delta:g}pp")
        else:
            decision = "keep-primary"
            reason = (f"accuracy parity but no modeled power win "
                      f"({power_delta:+g}pp)")
        return {
            "primary": self.primary_label,
            "shadow": self.shadow_label,
            "sampled_requests": self.sampled,
            "sampled_fraction": round(1.0 / self.every, 4),
            "tokens": self.tokens,
            "token_matches": self.matches,
            "token_match_rate": round(match_rate, 4),
            "logits_err_var": err_var,
            "logits_err_max_abs": self._max_abs,
            "primary_power_saving_pct": round(self.primary_saving_pct, 2),
            "shadow_power_saving_pct": round(self.shadow_saving_pct, 2),
            "power_delta_pct": power_delta,
            "verdict": decision,
            "reason": reason,
        }


def _pack_saving_pct(params) -> float:
    """MAC-weighted modeled array-power saving of one pack."""
    from repro.serving.engine import power_profile_from_params

    prof = power_profile_from_params(params)
    units = sum(e["mac_per_token"] for e in prof.values())
    saved = sum(e["mac_per_token"] * e["saving_pct"] / 100.0
                for e in prof.values())
    return 100.0 * saved / units if units else 0.0
