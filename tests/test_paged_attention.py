"""Paged-attention decode kernel: pallas (interpret) and jnp-ref parity
against the dense-gather oracle, scratch-page poisoning robustness,
dispatcher contracts, engine-level backend parity, and TP-over-kv-heads
composition via shard_map on ``make_debug_mesh``."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.config import ATTN, LOCAL, MLP, ModelConfig, RLConfig
from repro.kernels.ops import (paged_decode, paged_decode_layers,
                               paged_prefill, paged_prefill_layers)
from repro.kernels.paged_attention import paged_attention
from repro.models import init_params
from repro.sampling import generate, generate_continuous


def _tols(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


def make_case(*, b=4, hkv=2, rep=4, d=32, page=8, npages=6, pool=None,
              dtype=jnp.float32, seed=0, max_len=None):
    """Random pools + a block table of distinct physical pages per slot
    (page 0 reserved as scratch) + ragged per-slot lengths."""
    pool = pool or (1 + b * npages + 3)
    hq = hkv * rep
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, 1, hq, d), dtype)
    kp = jax.random.normal(ks[1], (pool, hkv, page, d), dtype)
    vp = jax.random.normal(ks[2], (pool, hkv, page, d), dtype)
    host = np.random.default_rng(seed)
    perm = host.permutation(np.arange(1, pool))
    table = perm[:b * npages].reshape(b, npages).astype(np.int32)
    hi = max_len or npages * page
    lengths = host.integers(1, hi + 1, size=b).astype(np.int32)
    return q, kp, vp, jnp.asarray(table), jnp.asarray(lengths)


def edge_lengths(npages, page, ppb=8):
    """Lengths at the kernel's edges, for a table of ``npages`` pages:
    0, 1, one page, one past it, one page block of ``ppb`` pages, one
    past it, the table's full reach, and one past that (the engine's
    dead-slot marker)."""
    block = min(ppb, npages) * page
    full = npages * page
    return np.asarray([0, 1, page, page + 1, block, block + 1, full,
                       full + 1], np.int32)


# (Hkv, rep, D, page): small shapes, then the rollout cells' heads —
# qwen3-1.7b (8 kv heads of 2 queries) and qwen3-30b-a3b (4 of 8)
SHAPES = {"g2r1p8": (2, 1, 32, 8), "g2r4p8": (2, 4, 32, 8),
          "g2r1p16": (2, 1, 32, 16), "g2r4p16": (2, 4, 32, 16),
          "qwen3-1.7b": (8, 2, 128, 16), "qwen3-30b-a3b": (4, 8, 128, 16)}


class TestParity:
    @pytest.mark.parametrize("shape", sorted(SHAPES))
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep_vs_gather_oracle(self, shape, dtype):
        """Every backend against the gather view at the kernel's edge
        lengths, with more than one page block in the table. Lengths 0
        and past the table's reach read nothing: the in-place backends
        return zeros there."""
        hkv, rep, d, page = SHAPES[shape]
        npages = 10
        lengths = edge_lengths(npages, page)
        q, kp, vp, table, _ = make_case(b=lengths.size, hkv=hkv, rep=rep,
                                        d=d, page=page, npages=npages,
                                        dtype=dtype, seed=hkv + rep + page)
        lengths = jnp.asarray(lengths)
        live = np.asarray((lengths > 0) & (lengths <= npages * page))
        oracle = np.asarray(paged_decode(q, kp, vp, table, lengths,
                                         impl="gather"), np.float32)
        for impl in ("ref", "pallas"):
            out = np.asarray(paged_decode(q, kp, vp, table, lengths,
                                          impl=impl, interpret=True),
                             np.float32)
            np.testing.assert_allclose(out[live], oracle[live],
                                       err_msg=impl, **_tols(dtype))
            np.testing.assert_array_equal(out[~live], 0.0, err_msg=impl)

    @pytest.mark.parametrize("window", [5, 16])
    def test_sliding_window_and_softcap(self, window):
        # two page blocks of the kernel: long slots' bands start in the
        # second, so the kernel skips the first
        q, kp, vp, table, _ = make_case(b=6, npages=10, seed=7)
        lengths = jnp.asarray([1, 9, 64, 65, 70, 80], jnp.int32)
        for cap in (None, 20.0):
            oracle = paged_decode(q, kp, vp, table, lengths, kind="local",
                                  window=window, softcap=cap, impl="gather")
            for impl in ("ref", "pallas"):
                out = paged_decode(q, kp, vp, table, lengths, kind="local",
                                   window=window, softcap=cap, impl=impl,
                                   interpret=True)
                np.testing.assert_allclose(
                    np.asarray(out), np.asarray(oracle), rtol=2e-5,
                    atol=2e-5, err_msg=f"{impl} cap={cap}")

    def test_ragged_lengths_match_per_slot_dense(self):
        """Each slot must attend exactly its first ``lengths[b]`` logical
        positions — checked against a per-slot dense softmax built from
        the table by hand."""
        q, kp, vp, table, lengths = make_case(b=3, rep=2, seed=11)
        out = np.asarray(paged_decode(q, kp, vp, table, lengths,
                                      impl="ref"), np.float32)
        tb, ln = np.asarray(table), np.asarray(lengths)
        g, d = kp.shape[1], kp.shape[3]                   # (P, Hkv, page, D)
        for b in range(q.shape[0]):
            kc = (np.asarray(kp, np.float32)[tb[b]].transpose(0, 2, 1, 3)
                  .reshape(-1, g, d))
            vc = (np.asarray(vp, np.float32)[tb[b]].transpose(0, 2, 1, 3)
                  .reshape(-1, g, d))
            kc, vc = kc[:ln[b]], vc[:ln[b]]
            qb = np.asarray(q, np.float32)[b, 0]          # (Hq, D)
            r = q.shape[2] // g
            qg = qb.reshape(g, r, -1)
            s = np.einsum("grd,kgd->grk", qg, kc) / np.sqrt(qb.shape[-1])
            p = np.exp(s - s.max(-1, keepdims=True))
            p /= p.sum(-1, keepdims=True)
            o = np.einsum("grk,kgd->grd", p, vc).reshape(qb.shape)
            np.testing.assert_allclose(out[b, 0], o, rtol=2e-5, atol=2e-5)


class TestScratchPoisoning:
    """Garbage (even NaN) in the scratch page / dead table tails must be
    causally invisible: live-slot outputs are bit-identical to a clean
    pool. (The dense-gather path fails this — 0 · NaN = NaN — which is
    exactly why the kernel zeroes masked values.)"""

    @pytest.mark.parametrize("impl", ["ref", "pallas"])
    def test_nan_scratch_page_invisible(self, impl):
        """NaN in every page a backend must not read: the scratch page,
        the pages of each slot's table past its live pages, and the
        whole row of a dead slot (length past the table's reach, the
        engine's marker for a finished slot). Live outputs stay bit for
        bit those of a clean pool; the dead slot reads zeros."""
        q, kp, vp, table, lengths = make_case(b=5, seed=3, npages=12,
                                              max_len=3 * 8)
        page = kp.shape[2]
        # dead tail of slots 0-1 parked on the scratch page, like the
        # engine's block table for partially-filled slots; slots 2-3
        # keep their own pages past their length
        tb = np.asarray(table).copy()
        tb[:2, 4:] = 0
        ln = np.asarray(lengths).copy()
        ln[4] = tb.shape[1] * page + 1
        tb, ln = jnp.asarray(tb), jnp.asarray(ln)
        clean = paged_decode(q, kp, vp, tb, ln, impl=impl, interpret=True)
        unread = {0} | {int(tb[s, j]) for s in range(4)
                        for j in range(-(-int(ln[s]) // page), tb.shape[1])}
        unread |= {int(p) for p in np.asarray(tb[4])}
        unread = jnp.asarray(sorted(unread))
        poisoned = paged_decode(q, kp.at[unread].set(jnp.nan),
                                vp.at[unread].set(jnp.nan), tb, ln,
                                impl=impl, interpret=True)
        assert bool(jnp.isfinite(poisoned).all())
        np.testing.assert_array_equal(np.asarray(poisoned),
                                      np.asarray(clean))
        np.testing.assert_array_equal(np.asarray(poisoned[4]), 0.0)

    @pytest.mark.parametrize("impl", ["ref", "pallas"])
    def test_dead_slot_yields_finite_output(self, impl):
        """A dead slot (whole row on scratch, length 1) — the engine's
        PAD-decoding idle slots — must not contaminate anything."""
        q, kp, vp, table, lengths = make_case(seed=5)
        tb = np.asarray(table).copy()
        tb[1, :] = 0
        ln = np.asarray(lengths).copy()
        ln[1] = 1
        out = paged_decode(q, kp, vp, jnp.asarray(tb), jnp.asarray(ln),
                           impl=impl, interpret=True)
        assert bool(jnp.isfinite(out).all())


class TestDispatcher:
    def test_unknown_impl_raises(self):
        q, kp, vp, table, lengths = make_case(b=1, npages=2)
        with pytest.raises(ValueError, match="unknown paged-attention"):
            paged_decode(q, kp, vp, table, lengths, impl="turbo")

    def test_bidir_rejected(self):
        q, kp, vp, table, lengths = make_case(b=1, npages=2)
        with pytest.raises(ValueError, match="causal-only"):
            paged_decode(q, kp, vp, table, lengths, kind="bidir")

    def test_auto_matches_ref_off_tpu(self):
        """Off a TPU "auto" (and None) decode through the gather view,
        bit for bit, and so agree with the ref backend to rounding."""
        q, kp, vp, table, lengths = make_case(seed=9)
        auto = paged_decode(q, kp, vp, table, lengths, impl="auto")
        gather = paged_decode(q, kp, vp, table, lengths, impl="gather")
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(gather))
        np.testing.assert_array_equal(
            np.asarray(paged_decode(q, kp, vp, table, lengths)),
            np.asarray(gather))
        ref = paged_decode(q, kp, vp, table, lengths, impl="ref")
        np.testing.assert_allclose(np.asarray(auto), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_window_ignored_unless_local(self):
        q, kp, vp, table, lengths = make_case(seed=13)
        causal = paged_decode(q, kp, vp, table, lengths, kind="causal",
                              window=4, impl="ref")
        nowin = paged_decode(q, kp, vp, table, lengths, kind="causal",
                             impl="ref")
        np.testing.assert_array_equal(np.asarray(causal), np.asarray(nowin))


def make_prefill_case(*, b=3, c=8, hkv=2, rep=4, d=32, page=8, npages=6,
                      dtype=jnp.float32, seed=0, starts=None):
    """Random pools + block table + *ragged chunk offsets*: slot s holds
    a C-token query chunk at absolute positions starts[s] + [0, C), and
    every position < starts[s] + C already has k/v in its pages (the
    engine scatters the chunk's k/v before attending)."""
    hq = hkv * rep
    pool = 1 + b * npages + 3
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (b, c, hq, d), dtype)
    kp = jax.random.normal(ks[1], (pool, hkv, page, d), dtype)
    vp = jax.random.normal(ks[2], (pool, hkv, page, d), dtype)
    host = np.random.default_rng(seed)
    perm = host.permutation(np.arange(1, pool))
    table = perm[:b * npages].reshape(b, npages).astype(np.int32)
    if starts is None:
        starts = host.integers(0, npages * page - c + 1, size=b)
    starts = np.asarray(starts, np.int32)
    positions = starts[:, None] + np.arange(c, dtype=np.int32)[None]
    return q, kp, vp, jnp.asarray(table), jnp.asarray(positions)


def _prefill_oracle(q, kp, vp, table, positions, *, window=None,
                    softcap=None):
    """Per-slot dense numpy softmax over the table's logical view, row
    i attending kv positions <= positions[s, i] (window band applied) —
    independent of every jax code path under test."""
    qn = np.asarray(q, np.float32)
    kpn, vpn = np.asarray(kp, np.float32), np.asarray(vp, np.float32)
    tb, pos = np.asarray(table), np.asarray(positions)
    b, c, hq, d = qn.shape
    g = kpn.shape[1]                                   # (P, G, page, D)
    rep = hq // g
    out = np.zeros_like(qn)
    for s in range(b):
        kc = kpn[tb[s]].transpose(0, 2, 1, 3).reshape(-1, g, d)  # (W·page,
        vc = vpn[tb[s]].transpose(0, 2, 1, 3).reshape(-1, g, d)  #  G, D)
        cols = np.arange(kc.shape[0])
        for i in range(c):
            ok = cols <= pos[s, i]
            if window is not None:
                ok &= cols > pos[s, i] - window
            for h in range(hq):
                sc = kc[:, h // rep] @ qn[s, i, h] / np.sqrt(d)
                if softcap is not None:
                    sc = softcap * np.tanh(sc / softcap)
                p = np.where(ok, np.exp(sc - sc[ok].max()), 0.0)
                p /= p.sum()
                out[s, i, h] = p @ np.where(ok[:, None], vc[:, h // rep], 0)
    return out


class TestPrefillParity:
    @pytest.mark.parametrize("page", [8, 16])
    @pytest.mark.parametrize("rep", [1, 4])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_sweep_vs_per_slot_dense(self, page, rep, dtype):
        q, kp, vp, table, positions = make_prefill_case(
            page=page, rep=rep, dtype=dtype, seed=page + rep)
        oracle = _prefill_oracle(q, kp, vp, table, positions)
        for impl in ("gather", "ref", "pallas"):
            out = paged_prefill(q, kp, vp, table, positions, impl=impl,
                                interpret=True)
            np.testing.assert_allclose(
                np.asarray(out, np.float32), oracle, err_msg=impl,
                **_tols(dtype))

    @pytest.mark.parametrize("window", [5, 16])
    def test_sliding_window_and_softcap(self, window):
        q, kp, vp, table, positions = make_prefill_case(seed=17)
        for cap in (None, 20.0):
            oracle = _prefill_oracle(q, kp, vp, table, positions,
                                     window=window, softcap=cap)
            for impl in ("gather", "ref", "pallas"):
                out = paged_prefill(q, kp, vp, table, positions,
                                    kind="local", window=window,
                                    softcap=cap, impl=impl, interpret=True)
                np.testing.assert_allclose(
                    np.asarray(out), oracle, rtol=2e-5, atol=2e-5,
                    err_msg=f"{impl} cap={cap}")

    def test_zero_offset_chunk(self):
        # a fresh prompt's first chunk: starts = 0 everywhere
        q, kp, vp, table, positions = make_prefill_case(
            starts=[0, 0, 0], seed=23)
        oracle = _prefill_oracle(q, kp, vp, table, positions)
        for impl in ("ref", "pallas"):
            out = paged_prefill(q, kp, vp, table, positions, impl=impl,
                                interpret=True)
            np.testing.assert_allclose(np.asarray(out), oracle,
                                       rtol=2e-5, atol=2e-5, err_msg=impl)

    def test_odd_chunk_width(self):
        # C that doesn't divide the default q block: _fit_block tiling
        q, kp, vp, table, positions = make_prefill_case(c=5, seed=29)
        oracle = _prefill_oracle(q, kp, vp, table, positions)
        out = paged_prefill(q, kp, vp, table, positions, impl="pallas",
                            interpret=True)
        np.testing.assert_allclose(np.asarray(out), oracle, rtol=2e-5,
                                   atol=2e-5)


class TestPrefillPoisoning:
    """NaN in the scratch page / unreachable table tails must be causally
    invisible to every prefill row — same contract as decode."""

    @pytest.mark.parametrize("impl", ["ref", "pallas"])
    def test_nan_scratch_page_invisible(self, impl):
        q, kp, vp, table, positions = make_prefill_case(
            starts=[0, 3, 9], seed=31)
        # park every page past the chunk's reach on the scratch page,
        # like the engine's table for a partially-prefilled slot
        page = kp.shape[2]
        tb = np.asarray(table).copy()
        pos = np.asarray(positions)
        for s in range(tb.shape[0]):
            live = -(-int(pos[s, -1] + 1) // page)
            tb[s, live:] = 0
        clean = paged_prefill(q, kp, vp, jnp.asarray(tb), positions,
                              impl=impl, interpret=True)
        poisoned = paged_prefill(q, kp.at[0].set(jnp.nan),
                                 vp.at[0].set(jnp.nan), jnp.asarray(tb),
                                 positions, impl=impl, interpret=True)
        assert bool(jnp.isfinite(poisoned).all())
        np.testing.assert_array_equal(np.asarray(poisoned),
                                      np.asarray(clean))


class TestPrefillDispatcher:
    def test_unknown_impl_raises(self):
        q, kp, vp, table, positions = make_prefill_case(b=1, npages=2, c=4)
        with pytest.raises(ValueError, match="unknown paged-attention"):
            paged_prefill(q, kp, vp, table, positions, impl="turbo")

    def test_bidir_rejected(self):
        q, kp, vp, table, positions = make_prefill_case(b=1, npages=2, c=4)
        with pytest.raises(ValueError, match="causal-only"):
            paged_prefill(q, kp, vp, table, positions, kind="bidir")

    def test_auto_matches_ref_off_tpu(self):
        """"auto" (and None) prefill through the gather view on every
        backend: bit for bit off a TPU, and within rounding of ref."""
        q, kp, vp, table, positions = make_prefill_case(seed=37)
        auto = paged_prefill(q, kp, vp, table, positions, impl="auto")
        gather = paged_prefill(q, kp, vp, table, positions, impl="gather")
        np.testing.assert_array_equal(np.asarray(auto), np.asarray(gather))
        np.testing.assert_array_equal(
            np.asarray(paged_prefill(q, kp, vp, table, positions)),
            np.asarray(gather))
        ref = paged_prefill(q, kp, vp, table, positions, impl="ref")
        np.testing.assert_allclose(np.asarray(auto), np.asarray(ref),
                                   rtol=2e-5, atol=2e-5)

    def test_auto_prefills_through_gather_on_tpu(self, monkeypatch):
        """On a TPU "auto" still prefills through the gather view: the
        prefill kernel is not the default there."""
        import repro.kernels.ops as ops_mod
        monkeypatch.setattr(ops_mod, "on_tpu", lambda: True)
        q, kp, vp, table, positions = make_prefill_case(seed=39)
        auto = paged_prefill.lower(q, kp, vp, table, positions,
                                   impl="auto").as_text()
        gather = paged_prefill.lower(q, kp, vp, table, positions,
                                     impl="gather").as_text()
        assert "tpu_custom_call" not in auto
        assert auto.replace('"auto"', '"gather"') == gather

    def test_no_dense_view_in_ref_lowering(self):
        """The point of the kernel: the ref path's XLA temp footprint
        must undercut the gather path's materialized
        (B, W·page, Hkv, D) logical view at wide tables."""
        q, kp, vp, table, positions = make_prefill_case(
            b=2, c=4, npages=24, page=8, starts=[0, 5], seed=41)
        args = (q, kp, vp, table, positions)

        def temp_bytes(impl):
            lowered = paged_prefill.lower(*args, impl=impl)
            return lowered.compile().memory_analysis().temp_size_in_bytes

        # table width 24 pages but only pages_for(5 + 4) = 2 live pages:
        # gather materializes the full-width view, ref streams per page
        assert temp_bytes("ref") * 4 < temp_bytes("gather")


class TestFusedLayers:
    """One launch for all layers' pools: the folded (L→slot axis) call
    must be bit-exact vs per-layer calls and issue exactly one
    pallas_call."""

    def _stacked(self, lyr=3, seed=43):
        qs, kps, vps = [], [], []
        for l in range(lyr):
            q, kp, vp, table, positions = make_prefill_case(
                seed=seed + 7 * l, starts=[2, 0, 11])
            qs.append(q), kps.append(kp), vps.append(vp)
        return (jnp.stack(qs), jnp.stack(kps), jnp.stack(vps), table,
                positions)

    @pytest.mark.parametrize("impl", ["gather", "ref", "pallas"])
    def test_prefill_fused_bitexact(self, impl):
        q, kp, vp, table, positions = self._stacked()
        per = jnp.stack([paged_prefill(q[l], kp[l], vp[l], table, positions,
                                       impl=impl, interpret=True)
                         for l in range(q.shape[0])])
        fused = paged_prefill_layers(q, kp, vp, table, positions,
                                     impl=impl, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(fused), np.asarray(per))  # noqa: RA003 — test sync

    @pytest.mark.parametrize("impl", ["gather", "ref", "pallas"])
    def test_decode_fused_bitexact(self, impl):
        q, kp, vp, table, positions = self._stacked()
        qd = q[:, :, :1]                                # (L, B, 1, Hq, D)
        lengths = positions[:, -1] + 1
        per = jnp.stack([paged_decode(qd[l], kp[l], vp[l], table, lengths,
                                      impl=impl, interpret=True)
                         for l in range(q.shape[0])])
        fused = paged_decode_layers(qd, kp, vp, table, lengths,
                                    impl=impl, interpret=True)
        np.testing.assert_array_equal(
            np.asarray(fused), np.asarray(per))  # noqa: RA003 — test sync

    def test_single_pallas_launch(self, monkeypatch):
        import repro.kernels.ops as ops_mod
        import repro.kernels.paged_attention as pa
        q, kp, vp, table, positions = self._stacked()
        lengths = positions[:, -1] + 1
        calls = []
        real = pa.pl.pallas_call

        def counting(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        monkeypatch.setattr(pa.pl, "pallas_call", counting)
        lyr = q.shape[0]
        qf, kpf, vpf, tbl, ln = ops_mod._fold_layers(
            q[:, :, :1], kp, vp, table, lengths)
        pa.paged_attention(qf[:, 0], kpf, vpf, tbl, ln, interpret=True)
        assert len(calls) == 1                  # ONE launch for L layers
        calls.clear()
        for l in range(lyr):
            pa.paged_attention(q[l, :, 0], kp[l], vp[l], table, lengths,
                               interpret=True)
        assert len(calls) == lyr


TINY = ModelConfig(name="tiny-paged", family="dense", num_layers=2,
                   d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                   vocab_size=32, block_pattern=(ATTN,), ffn_pattern=(MLP,),
                   dtype="float32", attn_impl="naive", remat=False,
                   rope_theta=1e4)

GQA_LOCAL = dataclasses.replace(TINY, name="tiny-paged-local", num_layers=4,
                                block_pattern=(ATTN, LOCAL),
                                sliding_window=6)


class TestEngineBackends:
    """The continuous engine run end-to-end under every paged backend
    must reproduce the static engine (the gather default bit-exactly;
    kernel/ref to float-reassociation tolerance — empirically exact at
    these scales)."""

    @pytest.mark.parametrize("impl", ["gather", "ref", "pallas"])
    def test_static_parity_all_impls(self, rng, impl):
        cfg = dataclasses.replace(TINY, paged_attn_impl=impl)
        params = init_params(cfg, rng)
        prompts = jax.random.randint(rng, (6, 5), 3, cfg.vocab_size)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0,
                      max_new_tokens=10)
        r1 = generate(cfg, rl, params, prompts, rng, vocab_limit=20)
        r2 = generate_continuous(cfg, rl, params, prompts, rng,
                                 vocab_limit=20, num_slots=3, page_size=4,
                                 sync_every=4)
        np.testing.assert_array_equal(np.asarray(r1["completions"]),
                                      np.asarray(r2["completions"]))
        np.testing.assert_allclose(np.asarray(r1["sampler_lp"]),
                                   np.asarray(r2["sampler_lp"]),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("impl", ["gather", "ref", "pallas"])
    def test_chunked_prefill_static_parity(self, rng, impl):
        """Chunked prefill (the paged_prefill hot path — ragged chunk
        offsets, narrowed tables) under every backend reproduces the
        static engine."""
        cfg = dataclasses.replace(TINY, paged_attn_impl=impl)
        params = init_params(cfg, rng)
        prompts = jax.random.randint(rng, (5, 9), 3, cfg.vocab_size)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=8)
        r1 = generate(cfg, rl, params, prompts, rng, vocab_limit=20)
        r2 = generate_continuous(cfg, rl, params, prompts, rng,
                                 vocab_limit=20, num_slots=2, page_size=4,
                                 prefill_chunk=4, sync_every=3)
        np.testing.assert_array_equal(np.asarray(r1["completions"]),
                                      np.asarray(r2["completions"]))
        np.testing.assert_allclose(np.asarray(r1["sampler_lp"]),
                                   np.asarray(r2["sampler_lp"]),
                                   rtol=1e-5, atol=1e-5)

    @pytest.mark.parametrize("impl", ["gather", "ref"])
    def test_prefix_cache_cow_pages(self, rng, impl):
        """Shared-prefix COW pages + chunked prefill: requests whose
        prompts share a prefix prefill against refcounted pages from
        `prefix_cache`; every backend must leave completions unchanged
        vs the uncached run."""
        cfg = dataclasses.replace(TINY, paged_attn_impl=impl)
        params = init_params(cfg, rng)
        base = np.asarray(jax.random.randint(rng, (1, 10), 3,
                                             cfg.vocab_size))
        prompts = np.repeat(base, 4, axis=0)
        prompts[2:, -2:] = [[3, 4], [5, 6]]    # diverge after the prefix
        prompts = jnp.asarray(prompts)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=6)
        cached = generate_continuous(cfg, rl, params, prompts, rng,
                                     vocab_limit=20, num_slots=2,
                                     page_size=4, prefill_chunk=4,
                                     sync_every=3, prefix_cache=True)
        plain = generate_continuous(cfg, rl, params, prompts, rng,
                                    vocab_limit=20, num_slots=2,
                                    page_size=4, prefill_chunk=4,
                                    sync_every=3, prefix_cache=False)
        np.testing.assert_array_equal(np.asarray(cached["completions"]),
                                      np.asarray(plain["completions"]))

    @pytest.mark.parametrize("program", ["prefill", "decode"])
    @pytest.mark.parametrize("impl", ["gather", "ref", "pallas"])
    def test_carried_pool_scan_bit_exact(self, impl, program):
        """The block scan carries the stacked pools and each block works
        on its slab in place. Against a scan that slices each block's
        slab in and writes it back out (the layout it replaced), every
        backend gives the same logits and the same pools, bit for bit.
        The pools hold random values, so reading another block's slab
        would show; the last slot's positions run past the table, so its
        writes drop."""
        from repro.models import decode_step, forward
        from repro.models.model import _apply_layer, _embed, _logits
        cfg = dataclasses.replace(GQA_LOCAL, num_layers=6,
                                  paged_attn_impl=impl)      # 3 blocks
        params = init_params(cfg, jax.random.PRNGKey(3))
        nb, npool, page, width = cfg.num_blocks, 13, 4, 4
        ks = jax.random.split(jax.random.PRNGKey(4), 2 * len(
            cfg.block_pattern))
        pool = {f"layer_{i}": {"self": {
            name: jax.random.normal(
                ks[2 * i + j], (nb, npool, cfg.num_kv_heads, page,
                                cfg.head_dim))
            for j, name in enumerate(("kp", "vp"))}}
            for i in range(len(cfg.block_pattern))}
        table = jnp.asarray(np.random.default_rng(5).permutation(
            np.arange(1, npool))[:3 * width].reshape(3, width), jnp.int32)
        tokens = jnp.asarray([[5, 7, 9, 11], [4, 6, 8, 10], [3, 12, 13, 14]])
        if program == "prefill":
            positions = jnp.asarray([0, 6, 14])[:, None] + jnp.arange(4)
        else:
            tokens, positions = tokens[:, :1], jnp.asarray([[3], [9], [16]])
        pos = positions[:, 0] if program == "decode" else None

        @jax.jit
        def carried(pool):
            if program == "decode":
                return decode_step(cfg, params, pool, tokens[:, 0], pos,
                                   page_table=table)
            logits, pool, _ = forward(cfg, params, tokens,
                                      positions=positions, cache=pool,
                                      page_table=table)
            return logits, pool

        @jax.jit
        def sliced(pool):
            def body(x, xs):
                bp, bc = xs
                new = {}
                for i in range(len(cfg.block_pattern)):
                    key = f"layer_{i}"
                    slab = jax.tree_util.tree_map(lambda a: a[None], bc[key])
                    x, nc, _ = _apply_layer(
                        cfg, i, bp[key], x, positions=positions,
                        memory=None, cache=slab, pos=pos, aux={},
                        page_table=table, block=0)
                    new[key] = jax.tree_util.tree_map(lambda a: a[0], nc)
                return x, new

            x, pool = jax.lax.scan(body, _embed(cfg, params, tokens),
                                   (params["blocks"], pool))
            logits = _logits(cfg, params, x)
            return (logits[:, 0] if program == "decode" else logits), pool

        got_logits, got_pool = carried(pool)
        want_logits, want_pool = sliced(pool)
        np.testing.assert_array_equal(np.asarray(got_logits),
                                      np.asarray(want_logits))
        for got, want in zip(jax.tree_util.tree_leaves(got_pool),
                             jax.tree_util.tree_leaves(want_pool)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_gqa_local_window_ref_backend(self, rng):
        cfg = dataclasses.replace(GQA_LOCAL, paged_attn_impl="ref")
        params = init_params(cfg, rng)
        prompts = jax.random.randint(rng, (4, 7), 3, cfg.vocab_size)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=8)
        r1 = generate(cfg, rl, params, prompts, rng, vocab_limit=20)
        r2 = generate_continuous(cfg, rl, params, prompts, rng,
                                 vocab_limit=20, num_slots=2, page_size=4,
                                 prefill_chunk=3, sync_every=3)
        np.testing.assert_array_equal(np.asarray(r1["completions"]),
                                      np.asarray(r2["completions"]))
        np.testing.assert_allclose(np.asarray(r1["sampler_lp"]),
                                   np.asarray(r2["sampler_lp"]),
                                   rtol=1e-3, atol=1e-3)


class TestEngineKvPages:
    """The engine's decode page counters and its backend choice."""

    def test_kv_page_counters(self, rng):
        """Four requests of known prompt lengths and token budgets, all
        admitted and prefilled in the first round: chunk k of
        ``sync_every`` steps decodes each unfinished request from
        position prompt + k·S for as many steps as its budget leaves,
        and step i attends ceil((pos + i + 1) / page) pages; the gather
        view reads slots x table width pages a step, the width being the
        live pages of the chunk's furthest position rounded up to a
        power of two."""
        from repro.sampling.continuous import ContinuousEngine
        from repro.serving.api import Request, SamplingParams
        page, sync, slots, budget = 4, 3, 6, 40
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0)
        eng = ContinuousEngine(TINY, init_params(TINY, rng), rl=rl,
                               max_total_tokens=budget, num_slots=slots,
                               page_size=page, sync_every=sync,
                               vocab_limit=20)
        prompts, max_new = [5, 9, 3, 12], [7, 2, 16, 10]
        host = np.random.default_rng(0)
        reqs = [Request(rid=r, prompt=host.integers(3, 20, n).astype(
                    np.int32),
                        params=SamplingParams(temperature=1.0, top_k=0,
                                              top_p=1.0, max_new_tokens=m))
                for r, (n, m) in enumerate(zip(prompts, max_new))]
        got = [len(res.tokens) for res in eng.generate(reqs, key=rng)]
        st = eng.stats()

        def ceil(a, b):
            return -(-a // b)

        read = table = 0
        for k in range(ceil(max(got), sync)):
            live = [r for r in range(len(reqs)) if got[r] > k * sync]
            far = max(prompts[r] + k * sync for r in live) + sync
            width = 1
            while width < ceil(far, page):
                width *= 2
            table += slots * min(width, ceil(budget, page)) * sync
            for r in live:
                steps = min(sync, max_new[r] - k * sync)
                read += sum(ceil(prompts[r] + k * sync + i + 1, page)
                            for i in range(steps))
        assert st["decode_kv_pages_read"] == read
        assert st["decode_kv_pages_table"] == table
        assert 0 < read < table

    @pytest.mark.parametrize("devices", [1, 2])
    def test_multi_device_plan_keeps_gather(self, rng, devices):
        """"auto" would run the Pallas kernel on a TPU, and a pallas_call
        has no GSPMD rule: an engine on a plan over more than one device
        decodes through the gather view instead. Explicit backends keep
        their meaning."""
        from types import SimpleNamespace

        from repro.sampling.continuous import ContinuousEngine
        params = init_params(TINY, rng)
        plan = SimpleNamespace(num_devices=devices)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0)
        want = "gather" if devices > 1 else "auto"
        for impl, expect in (("auto", want), ("pallas", "pallas"),
                             ("ref", "ref")):
            cfg = dataclasses.replace(TINY, paged_attn_impl=impl)
            eng = ContinuousEngine(cfg, params, rl=rl, max_total_tokens=16,
                                   num_slots=2, page_size=4, plan=plan)
            assert eng.cfg.paged_attn_impl == expect, impl
        assert ModelConfig.paged_attn_impl == "auto"


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >=2 devices (XLA_FLAGS="
                           "--xla_force_host_platform_device_count)")
class TestTensorParallel:
    """The kernel composes with the TP-over-kv-heads sharding the
    ExecutionPlan gives the kp/vp pools: per-shard dispatch via
    shard_map on a debug mesh reproduces the unsharded oracle."""

    def test_shard_map_kv_heads(self):
        from repro.parallel import make_debug_mesh
        mesh = make_debug_mesh(1, 2)
        q, kp, vp, table, lengths = make_case(hkv=2, rep=2, seed=21)

        # q heads are grouped per kv head ((B, 1, G·rep, D) with head
        # index g·rep + r), so sharding heads over 'model' keeps each
        # shard's q heads aligned with its kv heads.
        qs = P(None, None, "model", None)
        ps = P(None, "model", None, None)          # (pages, Hkv, page, D)

        def local(qx, kpx, vpx, tbl, ln):
            return paged_attention(qx[:, 0], kpx, vpx, tbl, ln,
                                   interpret=True)[:, None]

        fn = jax.shard_map(local, mesh=mesh,
                           in_specs=(qs, ps, ps, P(None, None), P(None)),
                           out_specs=qs, check_vma=False)
        fn_jit = jax.jit(fn)
        out = fn_jit(q, kp, vp, table, lengths)
        oracle = paged_decode(q, kp, vp, table, lengths, impl="gather")
        np.testing.assert_allclose(np.asarray(out), np.asarray(oracle),
                                   rtol=2e-5, atol=2e-5)

    def test_serve_plan_ref_backend(self):
        """The GSPMD-native ref backend under a real 1x2 serve plan —
        what `serve --mesh 1x4 --paged-attn-impl ref` runs."""
        from repro.parallel import ExecutionPlan, make_debug_mesh
        plan = ExecutionPlan(mesh=make_debug_mesh(1, 2), mode="serve")
        cfg = dataclasses.replace(TINY, paged_attn_impl="ref")
        key = jax.random.PRNGKey(0)
        params = plan.device_put_params(cfg, init_params(cfg, key))
        prompts = jax.random.randint(key, (4, 5), 3, cfg.vocab_size)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=6)
        roll = generate_continuous(cfg, rl, params, prompts, key,
                                   vocab_limit=20, num_slots=2,
                                   page_size=4, sync_every=2, plan=plan)
        ref1 = generate_continuous(cfg, rl, params, prompts, key,
                                   vocab_limit=20, num_slots=2,
                                   page_size=4, sync_every=2)
        np.testing.assert_array_equal(np.asarray(roll["completions"]),
                                      np.asarray(ref1["completions"]))

    def test_serve_plan_ref_backend_chunked_prefill(self):
        """Chunked prefill (paged_prefill_ref under the plan's sharding
        constraints) on a 1x2 serve plan matches the unplanned run."""
        from repro.parallel import ExecutionPlan, make_debug_mesh
        plan = ExecutionPlan(mesh=make_debug_mesh(1, 2), mode="serve")
        cfg = dataclasses.replace(TINY, paged_attn_impl="ref")
        key = jax.random.PRNGKey(1)
        params = plan.device_put_params(cfg, init_params(cfg, key))
        prompts = jax.random.randint(key, (4, 9), 3, cfg.vocab_size)
        rl = RLConfig(temperature=1.0, top_k=0, top_p=1.0, max_new_tokens=6)
        roll = generate_continuous(cfg, rl, params, prompts, key,
                                   vocab_limit=20, num_slots=2,
                                   page_size=4, prefill_chunk=3,
                                   sync_every=2, plan=plan)
        ref1 = generate_continuous(cfg, rl, params, prompts, key,
                                   vocab_limit=20, num_slots=2,
                                   page_size=4, prefill_chunk=3,
                                   sync_every=2)
        np.testing.assert_array_equal(np.asarray(roll["completions"]),
                                      np.asarray(ref1["completions"]))
