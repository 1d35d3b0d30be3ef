"""Fleet serving: spec-aware routing, cross-replica prefix sharing, and
the fleet-vs-single-engine token-identity contract.

Unit coverage (no model): router placement per policy on stub replicas —
latency class pinned to exact tiers, bulk to approximate tiers with
threshold spill into exact ones (never the reverse), least-loaded
scoring, validation errors — plus ``NumericsSpec.is_exact`` tier
classification and ``TierConfig`` validation.

Integration coverage (reduced model): prefix-cache export/import
roundtrip across two ``PagedKVPool``s (content equality, importer-side
refcount of exactly 1, idempotent re-import, LRU eviction of imported
blocks), an import-then-serve prefix hit that is token-identical to the
exporter, and the tentpole acceptance sweep — a two-tier fleet serving a
classed trace is token-identical, request by request, to single engines
packed per tier, under every routing policy.
"""

import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import EngineConfig
from repro.launch.serve import ServeConfig, build_serving_params
from repro.models import build_model
from repro.numerics import get_preset
from repro.serving import (FleetReplica, FleetRouter, ServingEngine,
                           TierConfig, build_fleet)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# ---------------------------------------------------------------------------
# router units (no model)
# ---------------------------------------------------------------------------


class _StubRequest:
    def __init__(self, rid):
        self.rid = rid


class _StubEngine:
    """The replica-handle surface the router touches, minus the model."""

    def __init__(self, numerics="int8", pending=0, ttft=None):
        self.numerics = numerics
        self.pending = pending
        self.ttft = ttft
        self.tracer = None
        self.submitted = []
        self._rid = 0

    def load(self):
        return {"queued": 0, "prefilling": 0, "decoding": 0,
                "pending": self.pending, "slots": 4, "slots_free": 4,
                "ttft_mean_s": self.ttft}

    def submit(self, prompt, max_new_tokens, priority=0, **kw):
        r = _StubRequest(self._rid)
        self._rid += 1
        self.submitted.append(r)
        self.pending += 1
        return r

    @property
    def idle(self):
        return True


def _stub_fleet(policy="spec-aware", spill_threshold=None,
                exact_counts=(2,), approx_counts=(2,)):
    reps = []
    for i in range(sum(exact_counts)):
        reps.append(FleetReplica(_StubEngine("int8"),
                                 TierConfig("exact", "int8", count=2),
                                 i, exact=True))
    for i in range(sum(approx_counts)):
        reps.append(FleetReplica(_StubEngine("serve-default"),
                                 TierConfig("bulk", "serve-default", count=2),
                                 i, exact=False))
    return FleetRouter(reps, policy=policy, spill_threshold=spill_threshold)


def test_spec_aware_routes_by_class():
    fl = _stub_fleet()
    lat = fl.submit([1, 2], 4, klass="latency")
    blk = fl.submit([1, 2], 4, klass="bulk")
    assert lat.fleet_tier == "exact" and not lat.fleet_spill
    assert blk.fleet_tier == "bulk" and not blk.fleet_spill
    assert fl.routed_by_class == {"latency": 1, "bulk": 1}


def test_class_derives_from_priority():
    fl = _stub_fleet()
    assert fl.submit([1], 4, priority=0).fleet_class == "latency"
    assert fl.submit([1], 4, priority=3).fleet_class == "bulk"


def test_least_loaded_within_home_tier_with_ttft_tiebreak():
    fl = _stub_fleet()
    exact = [r for r in fl.replicas if r.exact]
    exact[0].engine.pending = 3
    assert fl.submit([1], 4, klass="latency").fleet_replica == \
        exact[1].replica_id
    # equal pending: the faster-answering replica absorbs the request
    exact[0].engine.pending = exact[1].engine.pending
    exact[0].engine.ttft = 0.01
    exact[1].engine.ttft = 0.50
    assert fl.submit([1], 4, klass="latency").fleet_replica == \
        exact[0].replica_id


def test_bulk_spills_to_exact_past_threshold_latency_never():
    fl = _stub_fleet(spill_threshold=2)
    approx = [r for r in fl.replicas if not r.exact]
    for r in approx:
        r.engine.pending = 2  # bulk side saturated
    spilled = fl.submit([1], 4, klass="bulk")
    assert spilled.fleet_spill and spilled.fleet_tier == "exact"
    assert fl.spills == 1
    # exact side also at threshold: bulk stays home (spilling would only
    # move the queue, and the exact side serves latency traffic)
    for r in fl.replicas:
        r.engine.pending = 2
    stuck = fl.submit([1], 4, klass="bulk")
    assert not stuck.fleet_spill and stuck.fleet_tier == "bulk"
    # latency requests NEVER land on approximate replicas, loaded or not
    for _ in range(4):
        assert not fl.submit([1], 4, klass="latency").fleet_replica.startswith(
            "bulk")


def test_latency_without_exact_tier_raises():
    reps = [FleetReplica(_StubEngine("serve-default"),
                         TierConfig("bulk", "serve-default"), 0, exact=False)]
    fl = FleetRouter(reps)
    with pytest.raises(ValueError, match="exact tier"):
        fl.submit([1], 4, klass="latency")
    # bulk traffic on an all-approx fleet is fine
    assert fl.submit([1], 4, klass="bulk").fleet_tier == "bulk"


def test_bulk_without_approx_tier_runs_on_exact():
    reps = [FleetReplica(_StubEngine("int8"),
                         TierConfig("exact", "int8"), 0, exact=True)]
    fl = FleetRouter(reps)
    r = fl.submit([1], 4, klass="bulk")
    assert r.fleet_tier == "exact" and not r.fleet_spill


def test_round_robin_and_least_loaded_ignore_class():
    fl = _stub_fleet(policy="round-robin")
    seen = [fl.submit([1], 4, klass="latency").fleet_replica
            for _ in range(4)]
    assert len(set(seen)) == 4  # cycles the whole fleet
    fl = _stub_fleet(policy="least-loaded")
    for r in fl.replicas[:-1]:
        r.engine.pending = 5
    r = fl.submit([1], 4, klass="latency")
    assert r.fleet_replica == fl.replicas[-1].replica_id  # approx is fine


def test_router_and_tier_validation():
    with pytest.raises(ValueError, match="at least one replica"):
        FleetRouter([])
    reps = [FleetReplica(_StubEngine(), TierConfig("t", "int8"), 0, True)]
    with pytest.raises(ValueError, match="routing policy"):
        FleetRouter(reps, policy="nope")
    with pytest.raises(ValueError, match="spill_threshold"):
        FleetRouter(reps, spill_threshold=0)
    with pytest.raises(ValueError, match="count"):
        TierConfig("t", "int8", count=0)
    fl = FleetRouter(reps)
    with pytest.raises(ValueError, match="request class"):
        fl.submit([1], 4, klass="interactive")


def test_is_exact_classifies_tiers():
    assert get_preset("int8").is_exact
    assert not get_preset("serve-default").is_exact


# ---------------------------------------------------------------------------
# prefix export/import across pools (reduced model)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def olmo():
    cfg = dataclasses.replace(get_config("olmo-1b-reduced"),
                              compute_dtype="float32")
    api = build_model(cfg)
    return cfg, api, api.init(jax.random.PRNGKey(0))


def _engine(cfg, api, params, layout="paged", slots=3, max_len=64,
            chunk=16, bs=8, mesh=None, engine_id=None):
    return ServingEngine(cfg, params, EngineConfig(
        slots=slots, max_len=max_len, prefill_chunk=chunk,
        cache_dtype="float32", kv_layout=layout, kv_block_size=bs),
        api=api, mesh=mesh, engine_id=engine_id)


def test_prefix_export_import_roundtrip_refcounts_and_eviction(olmo):
    cfg, api, params = olmo
    rng = np.random.default_rng(3)
    prompt = rng.integers(0, cfg.vocab, 24).tolist()  # 3 full 8-blocks
    warm = _engine(cfg, api, params)
    warm.submit(prompt, 2)
    warm.drain()
    entries = warm.export_prefix()
    assert len(entries) == 3
    cold = _engine(cfg, api, params)
    imported = cold.import_prefix(entries)
    assert imported == 3
    assert cold.metrics.prefix_imports == 3
    # every imported block: registered under the exporter's chain hash,
    # content bit-identical, refcount exactly 1 (cache-held, evictable)
    held = dict(cold.pool.prefix.items())
    for h, content in entries:
        bid = held[h]
        assert cold.pool.allocator.refcount(bid) == 1
        for k, v in content.items():
            np.testing.assert_array_equal(
                np.asarray(cold.pool.cache[k][:, bid]), v)
    # idempotent: a second import of the same entries is a no-op
    assert cold.import_prefix(entries) == 0
    assert cold.metrics.prefix_imports == 3
    # importer-side eviction: refcount-1 entries are LRU-reclaimable
    free_before = cold.pool.allocator.n_free
    for _ in range(3):
        assert cold.pool.prefix.evict_lru(cold.pool.allocator)
    assert not cold.pool.prefix.evict_lru(cold.pool.allocator)
    assert cold.pool.allocator.n_free == free_before + 3


def test_import_then_serve_hits_and_matches_exporter_tokens(olmo):
    cfg, api, params = olmo
    rng = np.random.default_rng(4)
    shared = rng.integers(0, cfg.vocab, 24).tolist()
    suffix = rng.integers(0, cfg.vocab, 4).tolist()
    warm = _engine(cfg, api, params)
    warm.submit(shared, 2)
    warm.drain()
    ref = warm.submit(shared + suffix, 5)  # exporter serves from its cache
    warm.drain()
    cold = _engine(cfg, api, params)
    assert cold.import_prefix(warm.export_prefix()) > 0
    hit = cold.submit(shared + suffix, 5)
    cold.drain()
    # block-aligned shareable prefix, capped one token early
    assert hit.prefix_hit_tokens >= min(len(shared) // 8 * 8,
                                        len(shared) - 1)
    assert hit.generated == ref.generated


# ---------------------------------------------------------------------------
# fleet vs single engine: the token-identity acceptance sweep
# ---------------------------------------------------------------------------

_TIERS = ("int8", "serve-default")


@pytest.fixture(scope="module")
def packs(olmo):
    cfg, _, params = olmo
    return {name: build_serving_params(
        params, cfg, ServeConfig(spec=get_preset(name)))
        for name in _TIERS}


def _jobs(cfg, n=4, seed=6):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, cfg.vocab,
                          int(rng.integers(4, 22))).tolist(), 5)
            for _ in range(n)]


@pytest.fixture(scope="module")
def references(olmo, packs):
    """Per tier: the jobs served by ONE engine under that tier's pack."""
    cfg, api, _ = olmo
    jobs = _jobs(cfg)
    refs = {}
    for name in _TIERS:
        eng = _engine(cfg, api, packs[name], layout="contiguous")
        reqs = [eng.submit(p, g) for p, g in jobs]
        eng.drain()
        refs[name] = [r.generated for r in reqs]
    return jobs, refs


@pytest.mark.parametrize("policy",
                         ["spec-aware", "least-loaded", "round-robin"])
def test_fleet_token_identity_per_policy(olmo, packs, references, policy):
    cfg, api, _ = olmo
    jobs, refs = references
    ecfg = EngineConfig(slots=3, max_len=64, prefill_chunk=16,
                        cache_dtype="float32", kv_layout="contiguous")
    tiers = [TierConfig(name, name) for name in _TIERS]
    fleet = build_fleet(
        cfg, None, tiers, ecfg,
        pack=lambda name: (packs[name], name, get_preset(name)),
        api=api, policy=policy)
    placed = [fleet.submit(p, g, klass="bulk" if i % 2 else "latency")
              for i, (p, g) in enumerate(jobs)]
    fleet.drain()
    for i, r in enumerate(placed):
        # a request's tokens depend only on the tier that served it:
        # identical to a single engine under that tier's pack
        assert r.finish_reason == "length"
        assert r.generated == refs[r.fleet_tier][i], (policy, i)
        if policy == "spec-aware" and r.fleet_class == "latency":
            assert r.fleet_tier == "int8"  # exact tier only
    snap = fleet.snapshot()
    assert snap["fleet"]["numerics"] == "mixed"
    assert snap["fleet"]["engines"] == 2
    assert set(snap["tiers"]) == set(_TIERS)
    assert fleet.compile_count() <= 2 * len(fleet.replicas)


def test_fleet_share_prefixes_cross_replica(olmo, packs):
    cfg, api, _ = olmo
    ecfg = EngineConfig(slots=3, max_len=64, prefill_chunk=16,
                        cache_dtype="float32", kv_layout="paged",
                        kv_block_size=8)
    fleet = build_fleet(
        cfg, None, [TierConfig("int8", "int8", count=2)], ecfg,
        pack=lambda name: (packs[name], name, get_preset(name)), api=api)
    r0, r1 = fleet.replicas
    rng = np.random.default_rng(7)
    shared = rng.integers(0, cfg.vocab, 24).tolist()
    warm = r0.engine.submit(shared, 4)
    r0.engine.drain()
    assert fleet.share_prefixes() > 0
    hit = r1.engine.submit(shared, 4)
    r1.engine.drain()
    assert hit.prefix_hit_tokens == len(shared) - 1
    assert hit.generated == warm.generated
    snap = fleet.snapshot()
    assert snap["tiers"]["int8"]["prefix_imports"] > 0
    assert snap["fleet"]["prefix_imports"] == \
        snap["tiers"]["int8"]["prefix_imports"]


# ---------------------------------------------------------------------------
# placement: one replica per device (virtual CPU devices, one subprocess per
# device count so this process keeps its single device)
# ---------------------------------------------------------------------------

FLEET_PLACEMENT = textwrap.dedent("""
    import os, sys
    n_dev = int(sys.argv[1])
    os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    import dataclasses, json
    import jax
    import numpy as np
    from repro.configs import get_config
    from repro.configs.base import EngineConfig
    from repro.launch.serve import ServeConfig, build_serving_params
    from repro.models import build_model
    from repro.numerics import get_preset
    from repro.serving import TierConfig, build_fleet

    assert len(jax.devices()) == n_dev
    cfg = dataclasses.replace(get_config("olmo-1b-reduced"),
                              compute_dtype="float32")
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    names = ("int8", "serve-default")
    packs = {n: build_serving_params(params, cfg,
                                     ServeConfig(spec=get_preset(n)))
             for n in names}
    ecfg = EngineConfig(slots=3, max_len=64, prefill_chunk=16,
                        cache_dtype="float32", kv_layout="contiguous")
    rng = np.random.default_rng(6)
    jobs = [(rng.integers(0, cfg.vocab, int(rng.integers(4, 22))).tolist(), 5)
            for _ in range(8)]

    def homes(fleet):
        out = []
        for rep in fleet.replicas:
            on = lambda tree: {d for x in jax.tree.leaves(tree)
                               for d in x.devices()}
            p_dev, c_dev = on(rep.engine.params), on(rep.engine.pool.cache)
            assert len(p_dev) == 1 and p_dev == c_dev, (rep.replica_id,
                                                        p_dev, c_dev)
            out.append(p_dev.pop().id)
        return out

    fleet = build_fleet(cfg, params, [TierConfig(n, n, count=2)
                                      for n in names], ecfg,
                        pack=lambda n: (packs[n], n, get_preset(n)), api=api)
    placed_on = homes(fleet)  # before the first step
    placed = [fleet.submit(p, g, klass="bulk" if i % 2 else "latency")
              for i, (p, g) in enumerate(jobs)]
    fleet.drain()
    assert homes(fleet) == placed_on  # and after serving
    assert {r.fleet_replica for r in placed} == {
        rep.replica_id for rep in fleet.replicas}
    print(json.dumps({"devices": placed_on,
                      "tokens": [[r.fleet_replica, r.generated]
                                 for r in placed]}))
""")


def _fleet_placement(n_dev: int) -> dict:
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", FLEET_PLACEMENT, str(n_dev)],
                         capture_output=True, text=True, env=env,
                         cwd=REPO, timeout=280)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_fleet_places_one_replica_per_device():
    """Four replicas on four devices: each replica's params and KV cache
    sit on its own device, and the tokens match the one-device fleet."""
    four, one = _fleet_placement(4), _fleet_placement(1)
    assert four["devices"] == [0, 1, 2, 3]
    assert one["devices"] == [0, 0, 0, 0]
    assert four["tokens"] == one["tokens"]
