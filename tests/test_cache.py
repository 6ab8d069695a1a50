"""Unit matrix for the content-addressed cache (cluster/cache,
docs/caching.md): keys, the LRU/pinned store with checksummed
persistence, the in-flight coalescer, the conditioning wrapper, the
autoscaler pressure discount, and the API surface knobs.

The end-to-end properties (bit-identity through the real pipeline,
waiter fan-out, corruption under live load) live in
tests/test_cache_integration.py.
"""

import numpy as np
import pytest

from comfyui_distributed_tpu.cluster.cache import (
    CacheManager, build_cache_manager, cache_enabled)
from comfyui_distributed_tpu.cluster.cache import keys as ckeys
from comfyui_distributed_tpu.cluster.cache.coalesce import InflightCoalescer
from comfyui_distributed_tpu.cluster.cache.conditioning import (
    cached_encode, degraded, encoder_mode)
from comfyui_distributed_tpu.cluster.cache.store import CacheTier


# --- keys -------------------------------------------------------------------


def test_digest_is_boundary_safe():
    assert ckeys.digest("ab", "c") != ckeys.digest("a", "bc")


def test_canonical_bytes_is_order_insensitive():
    assert (ckeys.canonical_bytes({"a": 1, "b": [2, 3]})
            == ckeys.canonical_bytes({"b": [2, 3], "a": 1}))


def _prompt(seed=1, text="hello", negative=""):
    return {
        "1": {"class_type": "CheckpointLoader",
              "inputs": {"ckpt_name": "tiny"}},
        "2": {"class_type": "CLIPTextEncode",
              "inputs": {"text": text, "clip": ["1", 1]}},
        "3": {"class_type": "CLIPTextEncode",
              "inputs": {"text": negative, "clip": ["1", 1]}},
        "4": {"class_type": "TPUTxt2Img", "inputs": {
            "model": ["1", 0], "positive": ["2", 0], "negative": ["3", 0],
            "seed": seed, "steps": 2, "cfg": 2.0,
            "width": 16, "height": 16}},
    }


def test_fingerprint_covers_every_literal():
    base = ckeys.request_fingerprint(_prompt())
    assert ckeys.request_fingerprint(_prompt()) == base
    assert ckeys.request_fingerprint(_prompt(seed=2)) != base
    assert ckeys.request_fingerprint(_prompt(text="other")) != base
    assert ckeys.request_fingerprint(_prompt(negative="bad")) != base


def test_result_key_separates_conditioning_mode():
    fp = ckeys.request_fingerprint(_prompt())
    sig = ckeys.execution_signature()
    assert (ckeys.result_key(fp, sig, "bpe")
            != ckeys.result_key(fp, sig, "hash"))


def test_result_key_separates_weights_identity():
    """An in-place checkpoint swap (same ckpt_name, new mtime) must roll
    the result key — stale persisted images are invalidated, not
    served."""
    fp = ckeys.request_fingerprint(_prompt())
    sig = ckeys.execution_signature()
    assert (ckeys.result_key(fp, sig, "bpe", "tiny/ckpt:f.st:100")
            != ckeys.result_key(fp, sig, "bpe", "tiny/ckpt:f.st:200"))


def test_conditioning_key_separates_mode_and_encoder():
    sig = [[1, 2, 3]]
    assert (ckeys.conditioning_key("enc-a", sig, "l=bpe")
            != ckeys.conditioning_key("enc-a", sig, "l=hash"))
    assert (ckeys.conditioning_key("enc-a", sig, "l=bpe")
            != ckeys.conditioning_key("enc-b", sig, "l=bpe"))


def test_classifier_fingerprint_delegates():
    from comfyui_distributed_tpu.cluster.frontdoor.classifier import \
        fingerprint

    assert fingerprint(_prompt()) == ckeys.request_fingerprint(_prompt())


# --- store ------------------------------------------------------------------


def _arrays(n=16, fill=1.0):
    return {"images": np.full((n,), fill, np.float32)}


def test_store_roundtrip_memory():
    t = CacheTier("result", max_bytes=1 << 20)
    key = ckeys.digest("k1")
    assert t.get(key) is None
    t.put(key, _arrays())
    hit = t.get(key)
    assert np.array_equal(hit["images"], _arrays()["images"])
    assert t.counts["hit"] == 1 and t.counts["miss"] == 1


def test_store_lru_eviction_under_byte_cap():
    one = _arrays()["images"].nbytes
    t = CacheTier("result", max_bytes=2 * one)
    t.put("a", _arrays(fill=1))
    t.put("b", _arrays(fill=2))
    t.get("a")                      # a is now most-recently-used
    t.put("c", _arrays(fill=3))     # evicts b (LRU), not a
    assert t.get("a") is not None
    assert t.get("b") is None
    assert t.get("c") is not None
    assert t.counts["evicted"] == 1


def test_store_pin_blocks_eviction():
    one = _arrays()["images"].nbytes
    t = CacheTier("result", max_bytes=2 * one)
    t.put("a", _arrays(fill=1))
    assert t.pin("a")
    t.put("b", _arrays(fill=2))
    t.put("c", _arrays(fill=3))     # over budget; a is pinned → b evicts
    assert t.get("a") is not None   # (also refreshes a's LRU position)
    assert t.get("b") is None
    t.unpin("a")
    t.put("d", _arrays(fill=4))     # evicts c — the LRU unpinned entry
    assert t.get("c") is None
    t.put("e", _arrays(fill=5))     # a is now LRU and unpinned → evicted
    assert t.get("a") is None


def test_store_persists_and_reloads_across_instances(tmp_path):
    t = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    t.put("k", _arrays(fill=7))
    fresh = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    hit = fresh.get("k")
    assert hit is not None and np.array_equal(hit["images"],
                                              _arrays(fill=7)["images"])
    assert fresh.counts["disk_hit"] == 1


def test_store_checksum_rejects_corruption_loudly(tmp_path):
    t = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    t.put("k", _arrays(fill=7))
    path = t._entry_path("k")
    raw = bytearray(path.read_bytes())
    raw[len(raw) // 2] ^= 0xFF
    path.write_bytes(bytes(raw))
    fresh = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    assert fresh.get("k") is None          # rejected, never served
    assert fresh.counts["corrupt"] == 1
    # the entry is deleted everywhere: a recompute re-fills cleanly
    assert not path.exists()
    fresh.put("k", _arrays(fill=7))
    assert fresh.get("k") is not None


def test_store_truncated_sidecar_rejected(tmp_path):
    t = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    t.put("k", _arrays())
    t._entry_path("k").write_bytes(b"")
    fresh = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    assert fresh.get("k") is None
    assert fresh.counts["corrupt"] == 1


def test_store_index_merges_concurrent_writers(tmp_path):
    a = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    b = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    a.put("ka", _arrays(fill=1))
    b.put("kb", _arrays(fill=2))    # must not clobber ka's index row
    fresh = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    assert fresh.get("ka") is not None
    assert fresh.get("kb") is not None
    # the cross-PROCESS flock file exists next to the index
    assert (tmp_path / "result_index.lock").exists()


def test_store_index_cache_revalidates_on_external_write(tmp_path):
    """The hot-path index cache must notice another writer's merge (the
    file's mtime/size changes under os.replace) — a second controller's
    fresh entry is servable without restarting this one."""
    reader = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    assert reader.get("k-external") is None       # caches the empty index
    writer = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    writer.put("k-external", _arrays(fill=9))
    hit = reader.get("k-external")
    assert hit is not None and np.array_equal(
        hit["images"], _arrays(fill=9)["images"])


def test_store_disk_cap_evicts_oldest(tmp_path):
    one_payload = None
    t = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    t.put("k0", _arrays(fill=0))
    one_payload = t._read_index()["k0"]["bytes"]
    t.disk_max_bytes = 2 * one_payload + 1
    t.put("k1", _arrays(fill=1))
    t.put("k2", _arrays(fill=2))    # pushes k0 (oldest) off disk
    idx = t._read_index()
    assert "k0" not in idx and "k1" in idx and "k2" in idx


def test_store_non_persistable_dtype_stays_memory_only(tmp_path):
    import jax.numpy as jnp

    t = CacheTier("cond", max_bytes=1 << 20, directory=tmp_path)
    bf16 = np.asarray(jnp.ones((4,), jnp.bfloat16))
    t.put("k", {"context": bf16})
    assert "k" not in t._read_index()
    assert t.get("k") is not None      # memory hit still works


def test_store_clear_memory_keeps_disk(tmp_path):
    t = CacheTier("result", max_bytes=1 << 20, directory=tmp_path)
    t.put("k", _arrays())
    assert t.clear_memory() == 1
    assert t.entry_count == 0
    assert t.get("k") is not None      # reloaded from the persisted tier


# --- coalescer --------------------------------------------------------------


class _Member:
    def __init__(self, pid):
        self.prompt_id = pid


def test_coalescer_lead_join_resolve():
    c = InflightCoalescer()
    assert not c.join("fp", _Member("w1"))    # nothing in flight yet
    c.lead("fp", "leader")
    assert c.join("fp", _Member("w1"))
    assert c.join("fp", _Member("w2"))
    history = {"leader": {"status": "success", "outputs": {"4": (1,)}}}
    assert c.resolve(history) == 2
    assert history["w1"]["status"] == "success"
    assert history["w1"]["coalesced_with"] == "leader"
    assert history["w2"]["outputs"] == {"4": (1,)}
    assert c.inflight == 0 and c.coalesced_waiters == 2


def test_coalescer_error_and_interrupt_propagate():
    c = InflightCoalescer()
    c.lead("fp", "leader")
    c.join("fp", _Member("w"))
    history = {"leader": {"status": "error", "error": "boom"}}
    c.resolve(history)
    assert history["w"]["status"] == "error"


def test_coalescer_second_lead_is_noop():
    c = InflightCoalescer()
    c.lead("fp", "first")
    c.lead("fp", "second")
    c.join("fp", _Member("w"))
    history = {"first": {"status": "success"}}
    c.resolve(history)
    assert history["w"]["coalesced_with"] == "first"


def test_coalescer_unresolved_leader_keeps_waiting():
    c = InflightCoalescer()
    c.lead("fp", "leader")
    c.join("fp", _Member("w"))
    assert c.resolve({}) == 0
    assert c.pending_waiters == 1


class _DeadlineMember(_Member):
    def __init__(self, pid, deadline_at=None):
        super().__init__(pid)
        self.deadline_at = deadline_at

    def expired(self, now):
        return self.deadline_at is not None and now >= self.deadline_at


def test_coalescer_waiter_own_deadline_enforced():
    """deadline_ms is a freshness contract: a waiter whose own deadline
    passed while the leader ran must be recorded expired, not handed a
    stale success (a queued solo twin would have expired too)."""
    clock = {"t": 0.0}
    c = InflightCoalescer(clock=lambda: clock["t"])
    c.lead("fp", "leader")
    c.join("fp", _DeadlineMember("w-tight", deadline_at=5.0))
    c.join("fp", _DeadlineMember("w-loose", deadline_at=100.0))
    clock["t"] = 30.0
    history = {"leader": {"status": "success", "outputs": {"4": (1,)}}}
    c.resolve(history)
    assert history["w-tight"]["status"] == "expired"
    assert history["w-loose"]["status"] == "success"


def test_coalescer_expired_leader_redispatches_waiters():
    """A leader expiring on ITS deadline must not verdict a waiter that
    never asked for one: the waiter re-enters the batcher as a fresh
    execution (and becomes the new leader)."""
    c = InflightCoalescer()
    c.lead("fp", "leader")
    c.join("fp", _Member("w"), group_key="gk", sampler_node_id="4")
    history = {"leader": {"status": "expired",
                          "error": "deadline_ms elapsed before execution"}}
    redispatched = []
    c.resolve(history, redispatch=lambda m, gk, sid:
              redispatched.append((m.prompt_id, gk, sid)))
    assert redispatched == [("w", "gk", "4")]
    assert "w" not in history            # settled later, by its new run
    assert c.redispatched_waiters == 1


def test_coalescer_expired_leader_without_hook_errors_loudly():
    c = InflightCoalescer()
    c.lead("fp", "leader")
    c.join("fp", _Member("w"))
    history = {"leader": {"status": "expired"}}
    c.resolve(history)
    assert history["w"]["status"] == "error"
    assert "redispatch" in history["w"]["error"]


# --- conditioning wrapper ---------------------------------------------------


class _FakeEncoder:
    def __init__(self, ident="m/test/seed0", mode="hash-native"):
        if ident:
            self._cdt_encoder_id = ident
        self._tokenize_mode = mode
        self.calls = 0

    def token_signature(self, texts):
        return [[len(t) for t in texts]], self._tokenize_mode

    def encode(self, texts):
        import jax.numpy as jnp

        self.calls += 1
        return (jnp.full((len(texts), 4, 8), float(self.calls)),
                jnp.zeros((len(texts), 2)))


def _manager(tmp_path=None):
    return CacheManager(directory=tmp_path)


def test_cached_encode_hits_and_is_bit_identical():
    m = _manager()
    enc = _FakeEncoder()
    c1, p1 = cached_encode(m, enc, ["hello"])
    c2, p2 = cached_encode(m, enc, ["hello"])
    assert enc.calls == 1
    assert np.array_equal(np.asarray(c1), np.asarray(c2))
    assert np.array_equal(np.asarray(p1), np.asarray(p2))


def test_cached_encode_skips_unidentified_encoder():
    m = _manager()
    enc = _FakeEncoder(ident="")
    cached_encode(m, enc, ["hello"])
    cached_encode(m, enc, ["hello"])
    assert enc.calls == 2
    assert m.conditioning.entry_count == 0


def test_cached_encode_without_manager_passes_through():
    enc = _FakeEncoder()
    cached_encode(None, enc, ["x"])
    assert enc.calls == 1


def test_degraded_mode_never_persists(tmp_path):
    m = _manager(tmp_path)
    enc = _FakeEncoder(mode="l=hash,g=bpe")
    cached_encode(m, enc, ["hello"])
    assert m.conditioning.entry_count == 1          # memory entry exists
    assert m.conditioning._read_index() == {}       # but never on disk
    healthy = _FakeEncoder(mode="l=bpe,g=bpe")
    cached_encode(m, healthy, ["hello"])
    assert len(m.conditioning._read_index()) == 1   # healthy one persists


def test_degraded_mode_component_parse():
    assert degraded("l=hash,g=bpe")
    assert degraded("t5=hash")
    assert not degraded("l=bpe,g=bpe")
    assert not degraded("hash-native")   # by-design hash, not a fallback


def test_degraded_keys_never_collide_with_healthy():
    m = _manager()
    enc_h = _FakeEncoder(mode="l=hash")
    enc_b = _FakeEncoder(mode="l=bpe")
    cached_encode(m, enc_h, ["hello"])
    cached_encode(m, enc_b, ["hello"])
    assert enc_h.calls == 1 and enc_b.calls == 1    # no cross-mode hit
    assert m.conditioning.entry_count == 2


def test_encoder_mode_helper():
    from comfyui_distributed_tpu.models.text import (TextEncoder,
                                                     TextEncoderConfig)

    enc = TextEncoder(TextEncoderConfig.tiny())
    assert encoder_mode(enc) == "hash-native"
    assert encoder_mode(object()) == "unknown"


def test_real_encoders_expose_token_signature():
    import jax

    from comfyui_distributed_tpu.models.clip import (CLIPConditioner,
                                                     SDXLTextStack)
    from comfyui_distributed_tpu.models.text import (TextEncoder,
                                                     TextEncoderConfig)

    enc = TextEncoder(TextEncoderConfig.tiny()).init(jax.random.key(0))
    sig, mode = enc.token_signature(["a b", "c"])
    assert mode == "hash-native" and len(sig) == 2
    stack = SDXLTextStack.init_random(jax.random.key(1), tiny=True)
    cond = CLIPConditioner(stack, kind="sdxl")
    sig, mode = cond.token_signature(["a b"])
    assert len(sig) == 2           # per-tower id lists
    assert "hash" in mode or "bpe" in mode
    assert cond.tokenization_mode in ("bpe", "hash")


def test_registry_stamps_encoder_identity():
    from comfyui_distributed_tpu.models.registry import ModelRegistry

    bundle = ModelRegistry().get("tiny")
    ident = bundle.text_encoder._cdt_encoder_id
    assert ident.startswith("tiny/text/seed0")
    assert bundle.weights_identity().startswith("tiny/seed0")


def test_weights_swap_rolls_both_identities(tmp_path):
    """Loading checkpoint weights AFTER construction must re-stamp: a
    stale random-init identity would let a checkpoint-backed bundle
    share cache entries with a genuinely random-init twin (and vice
    versa across a shared CDT_CACHE_DIR)."""
    from comfyui_distributed_tpu.models.registry import ModelRegistry

    bundle = ModelRegistry().get("tiny")
    seed_ident = bundle.text_encoder._cdt_encoder_id
    seed_weights = bundle.weights_identity()
    ckpt = tmp_path / "tiny.safetensors"
    ckpt.write_bytes(b"x")
    # simulate what every checkpoint loader does: record provenance,
    # then re-stamp
    bundle._weights_source = ckpt
    bundle._stamp_text_encoder()
    assert bundle.text_encoder._cdt_encoder_id != seed_ident
    assert "ckpt:tiny.safetensors" in bundle.text_encoder._cdt_encoder_id
    assert bundle.weights_identity() != seed_weights
    assert "ckpt:tiny.safetensors" in bundle.weights_identity()


def test_bundle_seed_distinguishes_identities():
    from comfyui_distributed_tpu.models.registry import ModelBundle, PRESETS

    a = ModelBundle(PRESETS["tiny"], seed=0)
    b = ModelBundle(PRESETS["tiny"], seed=1)
    assert a.weights_identity() != b.weights_identity()
    assert a.text_encoder._cdt_encoder_id != b.text_encoder._cdt_encoder_id


def test_hash_tokenization_counter(monkeypatch):
    monkeypatch.setenv("CDT_TELEMETRY", "1")
    from comfyui_distributed_tpu.models.clip import (CLIPTextConfig,
                                                     tokenize_ids)
    from comfyui_distributed_tpu.telemetry.registry import REGISTRY

    def count():
        fam = REGISTRY.snapshot().get("cdt_hash_tokenization_total") or {}
        return sum(s.get("value", 0) for s in fam.get("series") or []
                   if (s.get("labels") or {}).get("tower") == "clip_l")

    before = count()
    cfg = CLIPTextConfig.tiny()
    tokenize_ids(["hello"], None, cfg, 0, tower="clip_l")
    assert count() == before + 1
    # signature tokenization must NOT double-count
    tokenize_ids(["hello"], None, cfg, 0, tower="clip_l", count=False)
    assert count() == before + 1


# --- manager / hit-rate window ----------------------------------------------


def test_manager_hit_rate_window():
    m = _manager()
    assert m.hit_rate() == 0.0
    for hit in (True, True, False, True):
        m.record_request(hit)
    assert m.hit_rate() == pytest.approx(0.75)
    stats = m.stats()
    assert stats["hit_rate"] == pytest.approx(0.75)
    assert "conditioning" in stats and "result" in stats


def test_build_cache_manager_kill_switch(monkeypatch):
    monkeypatch.setenv("CDT_CACHE", "0")
    assert not cache_enabled()
    assert build_cache_manager() is None
    monkeypatch.setenv("CDT_CACHE", "1")
    assert build_cache_manager() is not None


# --- autoscaler pressure discount -------------------------------------------


def test_effective_work_discounts_queue_by_hit_rate():
    from comfyui_distributed_tpu.cluster.elastic.autoscaler import \
        FleetSignals

    cold = FleetSignals(queue_depth=32, tile_depth=4, cache_hit_rate=0.0)
    hot = FleetSignals(queue_depth=32, tile_depth=4, cache_hit_rate=0.75)
    assert cold.effective_work == 36
    assert hot.effective_work == pytest.approx(32 * 0.25 + 4)
    # tile backlog is never discounted (tiles don't ride the cache)
    assert hot.effective_work > 32 * 0.25


def test_hot_cache_holds_fleet_cold_cache_scales_up():
    from comfyui_distributed_tpu.cluster.elastic.autoscaler import (
        AutoscalePolicy, Autoscaler, FleetSignals)

    policy = AutoscalePolicy(max_workers=8, scale_up_depth=4.0,
                             up_streak=2, up_cooldown_s=0.0)

    class Provider:
        def list_workers(self):
            return {}

        def scale_up(self):
            return "w-new"

        def scale_down(self, wid):
            pass

    def run(rate):
        sig = FleetSignals(queue_depth=32, tile_depth=0, active_workers=2,
                           cache_hit_rate=rate)
        clock = {"t": 0.0}
        scaler = Autoscaler(lambda: sig, Provider(), policy,
                            clock=lambda: clock["t"])
        decision = None
        # exactly up_streak ticks: the last one is the acting tick
        for _ in range(policy.up_streak):
            clock["t"] += 60.0
            decision = scaler.evaluate()
        return decision

    assert run(0.0).direction == "up"          # 32/3 > 4 → scale up
    assert run(0.9).direction == "hold"        # 3.2/3 < 4 → steady


def test_elastic_signals_carry_cache_hit_rate():
    from comfyui_distributed_tpu.cluster.elastic import ElasticManager

    class _Cache:
        def hit_rate(self):
            return 0.5

    class _Queue:
        queue_remaining = 3

    class _Store:
        tile_jobs = {}

    class _Provider:
        def list_workers(self):
            return {}

    class _Controller:
        cache = _Cache()
        queue = _Queue()
        store = _Store()
        frontdoor = None

    mgr = ElasticManager.__new__(ElasticManager)
    mgr.controller = _Controller()
    mgr.provider = _Provider()
    sig = mgr._signals()
    assert sig.cache_hit_rate == 0.5
    assert sig.effective_work == pytest.approx(1.5)


# --- API surface ------------------------------------------------------------


def test_queue_payload_cache_field():
    from comfyui_distributed_tpu.api.queue_request import \
        parse_queue_request_payload
    from comfyui_distributed_tpu.utils.exceptions import ValidationError

    base = {"prompt": {"1": {"class_type": "X"}}}
    assert parse_queue_request_payload(dict(base)).cache == "use"
    assert parse_queue_request_payload(
        dict(base, cache="bypass")).cache == "bypass"
    with pytest.raises(ValidationError, match="cache"):
        parse_queue_request_payload(dict(base, cache="refresh"))


# --- load_smoke dup-rate ----------------------------------------------------


def test_load_smoke_dup_rate_mix():
    import json as _json
    import os
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                    "scripts"))
    import load_smoke

    reqs = load_smoke.build_workload(7, 40, dup_rate=0.5)
    reqs2 = load_smoke.build_workload(7, 40, dup_rate=0.5)
    assert _json.dumps(reqs, sort_keys=True) == _json.dumps(
        reqs2, sort_keys=True)                       # seeded determinism
    prints = [_json.dumps(r["prompt"], sort_keys=True) for r in reqs]
    exact_dups = len(prints) - len(set(prints))
    assert exact_dups >= 5                           # byte-identical twins
    # near-duplicates: same text, different seed
    def text_of(p):
        prompt = _json.loads(p)
        return next(v["inputs"]["text"] for v in prompt.values()
                    if v["class_type"] == "CLIPTextEncode"
                    and v["inputs"]["text"])

    texts = [text_of(p) for p in prints]
    assert len(set(texts)) < len(set(prints))        # seed-rerolls exist
    none = load_smoke.build_workload(7, 40, dup_rate=0.0)
    prints0 = [_json.dumps(r["prompt"], sort_keys=True) for r in none]
    assert len(set(prints0)) == len(prints0)
