"""Serving front door: refcounted page allocator, shared-prefix cache
(bit-exactness + COW + eviction), SamplingParams/ServeConfig validation,
admission control reject paths, and the HTTP/websocket round-trip."""
import asyncio
import json

import jax
import numpy as np
import pytest

from repro.config import ATTN, MLP, ModelConfig, RLConfig, ServeConfig
from repro.models import init_params
from repro.sampling import (ContinuousEngine, PageAllocator, StaticEngine,
                            build_engine, pages_for)
from repro.sampling.prefix_cache import PrefixCache
from repro.serving import (EXPIRED, INFEASIBLE, OK, OVERLOADED, QUEUE_FULL,
                           AdmissionController)
from repro.serving.api import Engine, GenerationResult, Request, SamplingParams
from repro.serving.server import FrontDoor

TINY = ModelConfig(name="tiny-serve", family="dense", num_layers=2,
                   d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                   vocab_size=32, block_pattern=(ATTN,), ffn_pattern=(MLP,),
                   dtype="float32", attn_impl="naive", remat=False,
                   rope_theta=1e4)


def _prompt(rng, n):
    return rng.integers(4, 30, size=n).astype(np.int32)


# ---------------------------------------------------------------------------
class TestPageAllocator:
    """Refcounted allocator ≡ the old free-list for the single-owner
    pattern, plus retain/release semantics the prefix cache needs."""

    def test_alloc_free_roundtrip_matches_free_list(self):
        a = PageAllocator(8)
        avail0 = a.available
        pages = a.alloc(3)
        assert len(pages) == 3 and a.available == avail0 - 3
        a.free(pages)                      # legacy alias for release
        assert a.available == avail0
        assert sorted(a.alloc(avail0)) == sorted(range(1, 8))

    def test_double_free_raises(self):
        a = PageAllocator(8)
        pages = a.alloc(2)
        a.release(pages)
        with pytest.raises(ValueError, match="double free|foreign"):
            a.release(pages)

    def test_retain_keeps_page_alive_across_release(self):
        a = PageAllocator(8)
        (pg,) = a.alloc(1)
        a.retain([pg])
        assert a.refcount(pg) == 2
        assert a.release([pg]) == []       # still cache-held
        assert a.refcount(pg) == 1
        assert a.release([pg]) == [pg]     # last reference frees it
        with pytest.raises(ValueError):
            a.retain([pg])                 # retain of a dead page

    def test_alloc_insufficient_returns_none(self):
        a = PageAllocator(4)               # 3 usable (page 0 is scratch)
        assert a.alloc(5) is None
        assert a.available == 3            # failed alloc took nothing


# ---------------------------------------------------------------------------
class TestSamplingParamsValidation:
    def test_defaults_valid(self):
        assert SamplingParams().profile == (0.6, 20, 0.95)

    @pytest.mark.parametrize("kw", [
        {"temperature": -0.1}, {"temperature": float("nan")},
        {"top_k": -1}, {"top_p": 0.0}, {"top_p": 1.5},
        {"max_new_tokens": 0},
        {"temperature": 0.0, "top_k": 5},          # greedy + filter conflict
        {"temperature": 0.0, "top_p": 0.5},
    ])
    def test_invalid_combinations_raise(self, kw):
        with pytest.raises(ValueError):
            SamplingParams(**kw)

    def test_pure_greedy_allowed(self):
        sp = SamplingParams(temperature=0.0, top_k=0, top_p=1.0)
        assert sp.profile == (0.0, 0, 1.0)

    def test_rl_roundtrip(self):
        rl = RLConfig(temperature=0.8, top_k=7, top_p=0.9, max_new_tokens=5)
        sp = SamplingParams.from_rl(rl)
        assert sp.rl().temperature == 0.8 and sp.rl().max_new_tokens == 5

    @pytest.mark.parametrize("kw", [
        {"prompt": np.zeros((0,), np.int32)},
        {"prompt": np.zeros((2, 2), np.int32)},
        {"prompt": [1, 2], "priority": -1},
        {"prompt": [1, 2], "arrival_s": 5.0, "deadline_s": 4.0},
    ])
    def test_request_validation(self, kw):
        with pytest.raises(ValueError):
            Request(rid=0, **kw)


class TestServeConfig:
    @pytest.mark.parametrize("kw", [
        {"engine": "batch"}, {"num_slots": 0}, {"page_size": 0},
        {"max_total_tokens": 1}, {"max_queue": 0},
        {"queue_overcommit": 0.5},
    ])
    def test_invalid_raises(self, kw):
        with pytest.raises(ValueError):
            ServeConfig(**kw)

    def test_resolved_pages_headroom(self):
        base = ServeConfig(num_slots=2, page_size=4, max_total_tokens=16)
        off = ServeConfig(num_slots=2, page_size=4, max_total_tokens=16,
                          prefix_cache=False)
        assert base.pages_per_slot == 4
        assert off.resolved_num_pages == 1 + 8       # scratch + exact budget
        assert base.resolved_num_pages == 1 + 8 + 4  # +50% cache headroom
        explicit = ServeConfig(num_pages=99)
        assert explicit.resolved_num_pages == 99


# ---------------------------------------------------------------------------
class TestPrefixCache:
    def _cache(self, num_pages=32, page_size=4, **kw):
        alloc = PageAllocator(num_pages)
        return PrefixCache(page_size, alloc, **kw), alloc

    def test_insert_lookup_full_pages_and_cow_tail(self):
        cache, alloc = self._cache()
        rng = np.random.default_rng(0)
        prompt = _prompt(rng, 10)                    # 2 full pages + 2 tail
        pages = alloc.alloc(pages_for(10, 4))
        assert cache.insert(prompt, pages)
        sharer = np.concatenate([prompt, _prompt(rng, 3)])
        m, shared, cow = cache.lookup(sharer)
        assert m == 10 and shared == pages[:2] and cow == pages[2]
        aligned = np.concatenate([prompt[:8], 31 - prompt[8:]])
        m, shared, cow = cache.lookup(aligned)
        assert m == 8 and shared == pages[:2] and cow == -1

    def test_hit_capped_below_prompt_len(self):
        """The final prompt token always prefills — its logits seed
        decoding — so a fully-cached prompt still hits only len-1."""
        cache, alloc = self._cache()
        prompt = _prompt(np.random.default_rng(1), 8)
        cache.insert(prompt, alloc.alloc(2))
        m, _, _ = cache.lookup(prompt)
        assert m == 7

    def test_short_prompt_not_cached(self):
        cache, alloc = self._cache(page_size=8)
        assert not cache.insert(np.arange(4, dtype=np.int32), alloc.alloc(1))
        assert len(cache) == 0

    def test_peek_has_no_side_effects(self):
        cache, alloc = self._cache()
        prompt = _prompt(np.random.default_rng(2), 12)
        cache.insert(prompt, alloc.alloc(3))
        before = dict(cache.stats)
        m, shared, _ = cache.peek(np.concatenate([prompt, prompt[:2]]))
        assert m == 12 and len(shared) == 3
        assert cache.stats == before

    def test_lru_eviction_at_entry_cap(self):
        cache, alloc = self._cache(num_pages=64, max_entries=2)
        rng = np.random.default_rng(3)
        prompts = [_prompt(rng, 8) for _ in range(3)]
        for p in prompts:
            cache.insert(p, alloc.alloc(2))
        assert len(cache) == 2 and cache.stats["evictions"] == 1
        assert cache.lookup(prompts[0])[0] == 0      # LRU victim is gone
        assert cache.lookup(prompts[2])[0] == 7

    def test_evict_until_frees_pool(self):
        cache, alloc = self._cache(num_pages=9)      # 8 usable
        rng = np.random.default_rng(4)
        for _ in range(2):
            pages = alloc.alloc(4)
            cache.insert(pages=pages, prompt=_prompt(rng, 16))
            alloc.release(pages)                     # only the cache holds on
        assert alloc.available == 0
        assert cache.evict_until(6) == 2
        assert alloc.available == 8 and len(cache) == 0


# ---------------------------------------------------------------------------
def _serve(**kw):
    kw.setdefault("num_slots", 2)
    kw.setdefault("page_size", 4)
    kw.setdefault("sync_every", 4)
    kw.setdefault("max_total_tokens", 20)
    return ServeConfig(**kw)


def _engine(params, serve, rl, key):
    return build_engine(TINY, params, serve, rl=rl, vocab_limit=20, key=key)


@pytest.fixture(scope="module")
def tiny_params():
    return init_params(TINY, jax.random.PRNGKey(0))


class TestPrefixReuseEndToEnd:
    def test_prefix_hit_bit_exact_vs_cold_prefill(self, tiny_params, rng):
        """Requests served from cached prefix pages (incl. a COW tail)
        produce the same tokens and logps as a cold prefill."""
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=6,
                      engine="continuous")
        nrng = np.random.default_rng(7)
        prefix = _prompt(nrng, 10)                   # 2 full pages + 2 tail
        first = Request(rid=0, prompt=np.concatenate([prefix, [4, 5, 6]]),
                        params=SamplingParams.from_rl(rl))
        sharers = [Request(rid=r, prompt=np.concatenate(
                       [prefix, [10 + 3 * r, 7, 8]]),
                       params=SamplingParams.from_rl(rl))
                   for r in (1, 2)]
        results = {}
        for mode in (True, False):
            eng = _engine(tiny_params, _serve(prefix_cache=mode), rl, rng)
            eng.generate([first], key=rng)           # warm (or not) the cache
            results[mode] = eng.generate(sharers, key=rng)
            if mode:
                st = eng.stats()
                assert st["prefix_hits"] == 2
                assert st["prefix_tokens_reused"] == 20
                assert st["cow_copies"] == 2         # 10 % 4 != 0 → COW tail
        for warm, cold in zip(results[True], results[False]):
            np.testing.assert_array_equal(warm.tokens, cold.tokens)
            np.testing.assert_allclose(warm.logps, cold.logps,
                                       rtol=1e-5, atol=1e-5)
            assert warm.prefix_hit_tokens == 10
            assert cold.prefix_hit_tokens == 0

    def test_new_params_never_reuse_old_prefix_pages(self, tiny_params, rng):
        """After ``update_params`` (a sampler's weight push) a repeated
        prompt is prefilled under the new weights, exactly as a fresh
        engine holding them serves it."""
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=6,
                      engine="continuous")
        req = Request(rid=0, prompt=_prompt(np.random.default_rng(9), 10),
                      params=SamplingParams.from_rl(rl))
        new = init_params(TINY, jax.random.PRNGKey(1))
        eng = _engine(tiny_params, _serve(), rl, rng)
        eng.generate([req], key=rng)                 # caches the prefix
        eng.update_params(new)
        got = eng.generate([req], key=rng)[0]
        want = _engine(new, _serve(), rl, rng).generate([req], key=rng)[0]
        assert got.prefix_hit_tokens == 0
        np.testing.assert_array_equal(got.tokens, want.tokens)
        np.testing.assert_allclose(got.logps, want.logps, rtol=1e-5,
                                   atol=1e-5)

    def test_cache_evicted_under_pool_pressure(self, tiny_params, rng):
        """With an exact-budget pool (no headroom), cached prefixes must
        be evicted to admit new work — and everything still finishes
        with the pool balanced."""
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=4,
                      engine="continuous")
        serve = _serve(num_pages=1 + 2 * 5)          # scratch + 2 slots exact
        eng = _engine(tiny_params, serve, rl, rng)
        nrng = np.random.default_rng(8)
        reqs = [Request(rid=r, prompt=_prompt(nrng, 16),
                        params=SamplingParams.from_rl(rl))
                for r in range(6)]                   # all-distinct prompts
        out = eng.generate(reqs, key=rng)
        assert len(out) == 6
        assert all(r.finish_reason in ("eos", "length") for r in out)
        assert eng.prefix_cache.stats["evictions"] > 0
        held = len({pg for ent in eng.prefix_cache._entries.values()
                    for pg in ent.pages})
        assert eng.free_pages + held == eng.num_pages - 1


class TestAdmissionControl:
    def test_reject_taxonomy(self, tiny_params, rng):
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=8,
                      engine="continuous")
        serve = _serve(max_total_tokens=16, max_queue=3, queue_overcommit=1.0,
                       prefix_cache=False)
        eng = _engine(tiny_params, serve, rl, rng)
        adm = AdmissionController(serve, eng)
        sp = SamplingParams.from_rl(rl)
        ok = Request(rid=0, prompt=_prompt(np.random.default_rng(0), 8),
                     params=sp)
        assert adm.check(ok, now_s=0.0).reason == OK

        big = Request(rid=1, prompt=_prompt(np.random.default_rng(1), 12),
                      params=sp)                     # 12+8 > 16-token budget
        assert adm.check(big, now_s=0.0).reason == INFEASIBLE

        late = Request(rid=2, prompt=ok.prompt, params=sp, deadline_s=1.0)
        assert adm.check(late, now_s=2.0).reason == EXPIRED

        # queue 2 requests (8 pages promised) -> pool capacity 8 exceeded
        for r in (3, 4):
            eng.submit(Request(rid=r, prompt=ok.prompt, params=sp))
        assert adm.check(Request(rid=5, prompt=ok.prompt, params=sp),
                         now_s=0.0).reason == OVERLOADED
        eng.submit(Request(rid=6, prompt=ok.prompt, params=sp))
        assert adm.check(Request(rid=7, prompt=ok.prompt, params=sp),
                         now_s=0.0).reason == QUEUE_FULL
        assert adm.rejected_total == 4
        assert adm.rejected == {INFEASIBLE: 1, EXPIRED: 1, QUEUE_FULL: 1,
                                OVERLOADED: 1}
        eng.generate([], key=rng)                    # drain the queued three

    def test_shared_prefix_discounts_promised_pages(self, tiny_params, rng):
        """A request whose prefix is cached only charges admission for
        the pages it would newly allocate."""
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=4,
                      engine="continuous")
        serve = _serve(queue_overcommit=1.0)
        eng = _engine(tiny_params, serve, rl, rng)
        sp = SamplingParams.from_rl(rl)
        prompt = _prompt(np.random.default_rng(9), 16)
        eng.generate([Request(rid=0, prompt=prompt, params=sp)], key=rng)
        adm = AdmissionController(serve, eng)
        sharer = Request(rid=1, prompt=prompt.copy(), params=sp)
        cold = Request(rid=2, prompt=31 - prompt, params=sp)
        pages_cold = pages_for(16 + 4, 4)
        m, shared, _ = eng.prefix_cache.peek(sharer.prompt)
        assert len(shared) > 0
        assert adm.check(sharer, now_s=0.0).reason == OK
        assert adm.check(cold, now_s=0.0).reason == OK
        assert pages_cold - len(shared) < pages_cold  # the discount is real


class TestEngineProtocol:
    def test_both_engines_satisfy_protocol(self, tiny_params, rng):
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=4)
        cont = _engine(tiny_params, _serve(), rl, rng)
        stat = _engine(tiny_params, _serve(engine="static"), rl, rng)
        assert isinstance(cont, ContinuousEngine)
        assert isinstance(stat, StaticEngine)
        assert isinstance(cont, Engine) and isinstance(stat, Engine)
        sp = SamplingParams.from_rl(rl)
        reqs = [Request(rid=r, prompt=np.arange(4, 10, dtype=np.int32),
                        params=sp) for r in range(2)]
        for eng in (cont, stat):
            out = eng.generate(reqs, key=rng)
            assert [r.rid for r in out] == [0, 1]
            assert all(isinstance(r, GenerationResult) for r in out)


# ---------------------------------------------------------------------------
class TestFrontDoor:
    """HTTP + websocket round-trip against an in-process FrontDoor."""

    def _door(self, tiny_params):
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=5,
                      engine="continuous")
        serve = _serve(port=0, max_total_tokens=16)
        return FrontDoor(TINY, tiny_params, serve, rl=rl, vocab_limit=20,
                         key=jax.random.PRNGKey(3))

    async def _http(self, port, method, path, payload=None):
        reader, writer = await asyncio.open_connection("127.0.0.1", port)
        body = json.dumps(payload).encode() if payload is not None else b""
        writer.write((f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
                      f"Content-Length: {len(body)}\r\n\r\n"
                      ).encode() + body)
        await writer.drain()
        status = int((await reader.readline()).split()[1])
        n = 0
        while True:
            line = await reader.readline()
            if line in (b"\r\n", b""):
                break
            if line.lower().startswith(b"content-length:"):
                n = int(line.split(b":")[1])
        data = await reader.readexactly(n)
        writer.close()
        return status, json.loads(data)

    def test_http_generate_metrics_and_rejection(self, tiny_params):
        async def scenario():
            door = self._door(tiny_params)
            await door.start()
            try:
                status, out = await self._http(
                    door.port, "POST", "/generate",
                    {"tokens": [5, 6, 7, 8], "max_new_tokens": 5})
                assert status == 200
                assert len(out["tokens"]) == len(out["logps"]) >= 1
                assert out["finish_reason"] in ("eos", "length")

                status, err = await self._http(
                    door.port, "POST", "/generate",
                    {"tokens": list(range(4, 18)), "max_new_tokens": 5})
                assert status == 400                 # infeasible: 14+5 > 16
                assert err["error"] == INFEASIBLE

                status, health = await self._http(door.port, "GET", "/healthz")
                assert status == 200 and health["ok"]
                status, m = await self._http(door.port, "GET", "/metrics")
                assert status == 200
                assert m["slo"]["completed"] == 1
                assert m["rejected"][INFEASIBLE] == 1
                assert m["engine"]["completed"] == 1
            finally:
                await door.close()
        asyncio.run(scenario())

    def test_metrics_prometheus_exposition(self, tiny_params):
        """GET /metrics negotiates Prometheus text (Accept: text/plain or
        ?format=prometheus) while the JSON snapshot stays the default;
        the text carries the unified registry: serve SLO counters,
        engine page-pool gauges, and the compile-sentinel mirror."""
        from repro import obs

        async def scenario():
            door = self._door(tiny_params)
            await door.start()
            obs.configure(True, clear=True)
            try:
                status, _ = await self._http(
                    door.port, "POST", "/generate",
                    {"tokens": [5, 6, 7, 8], "max_new_tokens": 5})
                assert status == 200
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", door.port)
                writer.write(b"GET /metrics?format=prometheus HTTP/1.1\r\n"
                             b"Host: t\r\nAccept: text/plain\r\n\r\n")
                await writer.drain()
                assert b"200" in await reader.readline()
                ctype, n = b"", 0
                while True:
                    line = await reader.readline()
                    if line in (b"\r\n", b""):
                        break
                    if line.lower().startswith(b"content-type:"):
                        ctype = line
                    if line.lower().startswith(b"content-length:"):
                        n = int(line.split(b":")[1])
                text = (await reader.readexactly(n)).decode()
                writer.close()
                assert b"text/plain" in ctype
                assert "# TYPE serve_requests_completed_total counter" \
                    in text
                assert "serve_requests_completed_total 1" in text
                assert "serve_ttft_seconds_bucket" in text
                assert "engine_free_pages" in text        # page pool
                assert "xla_compiles_total" in text       # sentinel mirror
                # default (no Accept/format) still answers JSON
                status, m = await self._http(door.port, "GET", "/metrics")
                assert status == 200 and m["slo"]["completed"] == 1
            finally:
                obs.configure(False, clear=True)
                await door.close()
        asyncio.run(scenario())

    def test_websocket_stream(self, tiny_params):
        async def scenario():
            door = self._door(tiny_params)
            await door.start()
            try:
                reader, writer = await asyncio.open_connection(
                    "127.0.0.1", door.port)
                writer.write(b"GET /ws HTTP/1.1\r\nHost: t\r\n"
                             b"Upgrade: websocket\r\nConnection: Upgrade\r\n"
                             b"Sec-WebSocket-Key: dGVzdGtleTEyMzQ1Njc4\r\n"
                             b"\r\n")
                await writer.drain()
                assert b"101" in await reader.readline()
                while (await reader.readline()) not in (b"\r\n", b""):
                    pass
                payload = json.dumps({"id": "a", "tokens": [5, 6, 7],
                                      "max_new_tokens": 5}).encode()
                mask = b"\x01\x02\x03\x04"
                frame = bytes([0x81, 0x80 | len(payload)]) + mask + bytes(
                    b ^ mask[i % 4] for i, b in enumerate(payload))
                writer.write(frame)
                await writer.drain()
                events = []
                while True:                          # server frames: unmasked
                    hdr = await reader.readexactly(2)
                    ln = hdr[1] & 0x7F
                    if ln == 126:
                        ln = int.from_bytes(await reader.readexactly(2),
                                            "big")
                    events.append(json.loads(await reader.readexactly(ln)))
                    if "finish_reason" in events[-1]:
                        break
                assert all(e["id"] == "a" for e in events)
                assert events[-1]["finish_reason"] in ("eos", "length")
                assert [e["token"] for e in events[:-1]] == \
                    events[-1]["tokens"][:len(events) - 1]
                writer.close()
            finally:
                await door.close()
        asyncio.run(scenario())
