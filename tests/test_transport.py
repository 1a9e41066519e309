"""Shard-streamed weight transport (repro.transport): chunk codec
byte-exactness (bf16/exotic dtypes included), delta-sync determinism,
resume-after-drop, payload-aware delays, PolicyStore chunk-index GC +
bounded bookkeeping, and (in a forced-device subprocess) elastic re-fit
parity of a sampler on a smaller plan against the whole-blob path."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import PolicyStore, load_pytree, save_pytree
from repro.checkpoint.store import path_key
from repro.config import ATTN, MLP, HeteroConfig, ModelConfig
from repro.hetero.latency import sample_delay, sync_delay_s
from repro.models import init_params
from repro.parallel import local_plan
from repro.transport import (ChunkSubscriber, Manifest, SimulatedLink,
                             SyncInterrupted, assemble_leaf, chunk_host_leaf,
                             publish_params)

TINY = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=48,
                   num_heads=4, num_kv_heads=2, d_ff=96, vocab_size=32,
                   block_pattern=(ATTN,), ffn_pattern=(MLP,),
                   dtype="float32", attn_impl="naive", remat=False,
                   rope_theta=1e4)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _leaf_roundtrip(arr):
    sharding = local_plan("serve").replicated
    parts = chunk_host_leaf(arr, sharding)
    back = assemble_leaf(str(arr.dtype), tuple(arr.shape), parts)
    host = np.asarray(arr)
    assert back.dtype == host.dtype
    assert back.tobytes() == np.ascontiguousarray(host).tobytes()
    return parts


class TestChunkCodec:
    @pytest.mark.parametrize("dtype", ["float32", "int32", "bfloat16",
                                       "float16"])
    def test_roundtrip_byte_exact(self, dtype):
        x = (jnp.arange(24, dtype=jnp.float32) * 0.37 - 3).reshape(4, 6)
        arr = x.astype(dtype)
        parts = _leaf_roundtrip(arr)
        assert sum(r.nbytes for r, _ in parts) == np.asarray(arr).nbytes

    def test_roundtrip_exotic_float8(self):
        if not hasattr(jnp, "float8_e4m3fn"):
            pytest.skip("float8 not available in this jax")
        arr = jnp.arange(16, dtype=jnp.float32).astype(jnp.float8_e4m3fn)
        _leaf_roundtrip(arr)

    def test_scalar_and_odd_shapes(self):
        _leaf_roundtrip(jnp.float32(2.5))
        _leaf_roundtrip(jnp.arange(7, dtype=jnp.bfloat16))

    def test_content_hash_deterministic(self):
        sharding = local_plan("serve").replicated
        a = jnp.arange(12, dtype=jnp.float32).reshape(3, 4)
        h1 = [r.hash for r, _ in chunk_host_leaf(a, sharding)]
        h2 = [r.hash for r, _ in chunk_host_leaf(jnp.array(a), sharding)]
        assert h1 == h2
        h3 = [r.hash for r, _ in chunk_host_leaf(a + 1, sharding)]
        assert h1 != h3


class TestDeltaSync:
    def _publish_sync(self):
        params = init_params(TINY, jax.random.PRNGKey(0))
        store = PolicyStore()
        plan = local_plan("train")
        st0 = publish_params(store, 0, plan, TINY, params)
        link = SimulatedLink()
        sub = ChunkSubscriber(store, link)
        return params, store, plan, st0, link, sub

    def test_same_params_move_zero_chunks(self):
        params, store, plan, st0, link, sub = self._publish_sync()
        _, tree0, s0 = sub.sync(params, cfg=TINY, plan=local_plan("serve"))
        # cold: full fetch of every distinct chunk (identical-content
        # leaves dedup even within one publish, hence bytes_new)
        assert s0.chunk_bytes == st0.bytes_new
        st1 = publish_params(store, 1, plan, TINY, params)
        assert st1.bytes_new == 0 and st1.chunks_new == 0
        v, tree1, s1 = sub.sync(params, cfg=TINY, plan=local_plan("serve"))
        assert v == 1
        assert s1.chunk_bytes == 0 and s1.chunks_fetched == 0
        assert s1.dedup_ratio == 1.0
        assert s1.bytes_on_wire == s1.manifest_bytes      # manifest only
        for a, b in zip(jax.tree_util.tree_leaves(tree0),
                        jax.tree_util.tree_leaves(tree1)):
            np.testing.assert_array_equal(a, b)

    def test_partial_change_moves_only_changed_chunks(self):
        params, store, plan, st0, link, sub = self._publish_sync()
        sub.sync(params, cfg=TINY, plan=local_plan("serve"))

        def bump(path, leaf):
            return leaf + 1.0 if "attn" in path_key(path) else leaf

        p2 = jax.tree_util.tree_map_with_path(bump, params)
        st2 = publish_params(store, 1, plan, TINY, p2)
        assert 0 < st2.bytes_new < st2.payload_bytes
        _, tree, s2 = sub.sync(p2, cfg=TINY, plan=local_plan("serve"))
        assert s2.chunk_bytes == st2.bytes_new            # exactly the delta
        # restore byte-identical to the legacy whole-blob path
        legacy = load_pytree(save_pytree(p2), p2)
        for a, b in zip(jax.tree_util.tree_leaves(legacy),
                        jax.tree_util.tree_leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_resume_after_drop(self):
        params, store, plan, st0, _, _ = self._publish_sync()
        link = SimulatedLink(drop_after_bytes=st0.bytes_new // 3)
        sub = ChunkSubscriber(store, link)
        with pytest.raises(SyncInterrupted, match="resumes"):
            sub.sync(params, cfg=TINY, plan=local_plan("serve"))
        partial = link.bytes_on_wire
        assert 0 < partial < st0.bytes_new
        v, tree, ss = sub.sync(params, cfg=TINY, plan=local_plan("serve"))
        assert ss.bytes_resumed > 0
        # no chunk byte was paid twice: total wire = one copy of every
        # distinct chunk plus one manifest per attempt
        assert link.bytes_on_wire == (st0.bytes_new
                                      + 2 * ss.manifest_bytes)
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


class TestPayloadAwareDelay:
    def test_inf_bandwidth_bit_compatible(self):
        hcfg = HeteroConfig(delay_distribution="lognormal",
                            delay_median_s=120.0)
        d1 = [sample_delay(np.random.default_rng(3), hcfg)
              for _ in range(16)]
        d2 = [sync_delay_s(np.random.default_rng(3), hcfg, 10**9)
              for _ in range(16)]
        # same rng draw, no payload term at bandwidth inf
        assert d1 == d2

    def test_payload_adds_serialization_time(self):
        hcfg = HeteroConfig(delay_distribution="constant",
                            delay_median_s=60.0, bandwidth_mbps=100.0)
        rng = np.random.default_rng(0)
        base = sync_delay_s(rng, hcfg, 0)
        loaded = sync_delay_s(rng, hcfg, 10**8)       # 100 MB at 100 Mbps
        assert base == 60.0
        assert loaded == pytest.approx(60.0 + 8.0)


class TestPolicyStoreBookkeeping:
    def test_bytes_published_counts_net_new_only(self):
        store = PolicyStore()
        store.publish(0, b"abcd")
        store.publish(0, b"abcd")                 # re-publish: no growth
        assert store.bytes_published == 4
        store.publish(0, b"abcdef")               # replaced: delta only
        assert store.bytes_published == 6

    def test_published_set_bounded_with_degrade_below_horizon(self):
        store = PolicyStore(keep=2, track=8)
        for v in range(30):
            store.publish(v, bytes([v]))
        assert len(store._published) <= 8
        v, _ = store.fetch(0)                     # below horizon: degrade
        assert v == 28 and store.stale_fetches == 1
        with pytest.raises(KeyError, match="never published"):
            store.fetch(40)                       # beyond latest: error

    def test_chunk_gc_on_manifest_prune(self):
        from repro.transport import ChunkRef, content_hash
        from repro.transport.manifest import LeafManifest
        store = PolicyStore(keep=2)
        for v in range(6):
            data = bytes([v]) * 8
            h = content_hash(data)
            store.put_chunk(h, data)
            m = Manifest(version=v, leaves=(LeafManifest(
                key="w", dtype="uint8", shape=(8,),
                chunks=(ChunkRef(hash=h, nbytes=8, start=(0,),
                                 shape=(8,)),)),))
            store.publish_manifest(v, m.to_json(), m.hashes())
        # only the chunks of the 2 retained manifests survive
        assert store.num_chunks == 2
        assert store.chunks_gced == 4

    def test_publish_manifest_requires_chunks(self):
        store = PolicyStore()
        m = Manifest(version=0, leaves=())
        store.publish_manifest(0, m.to_json(), m.hashes())   # empty ok
        with pytest.raises(KeyError, match="put_chunk first"):
            store.publish_manifest(1, b"{}", ["deadbeef"])


class TestSamplerRefit:
    def test_refit_with_empty_store_keeps_plan_and_params_consistent(self):
        """sync(plan=...) before anything is published must still re-place
        the live params onto the new plan — plan and placement may never
        disagree."""
        from repro.config import RLConfig
        from repro.data import ArithmeticTask, PromptPipeline, Tokenizer
        from repro.hetero.nodes import SamplerNode
        params = init_params(TINY, jax.random.PRNGKey(0))
        task = ArithmeticTask(max_operand=9, ops="+", prompt_width=5,
                              seed=0)
        tok = Tokenizer()
        s = SamplerNode(0, TINY, RLConfig(group_size=4),
                        PromptPipeline(task, tok, 4, 4), task, tok,
                        params, PolicyStore(), HeteroConfig(num_samplers=1),
                        seed=0)
        new_plan = local_plan("long")
        assert s.sync(plan=new_plan) == 0          # nothing to fetch
        assert s.plan is new_plan
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(s.params)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, numpy as np
    from repro.checkpoint import PolicyStore, load_pytree, save_pytree
    from repro.config import (ATTN, MLP, HeteroConfig, ModelConfig,
                              RLConfig, TrainConfig)
    from repro.models import init_params
    from repro.parallel import ExecutionPlan, make_debug_mesh, make_mesh
    from repro.transport import ChunkSubscriber, Manifest, publish_params

    cfg = ModelConfig(name="tiny", family="dense", num_layers=2,
                      d_model=48, num_heads=4, num_kv_heads=2, d_ff=96,
                      vocab_size=32, block_pattern=(ATTN,),
                      ffn_pattern=(MLP,), dtype="float32",
                      attn_impl="naive", remat=False, rope_theta=1e4)
    learner_plan = ExecutionPlan(mesh=make_debug_mesh(2, 2), mode="train")
    plan_12 = ExecutionPlan(mesh=make_mesh((1, 2), ("data", "model")),
                            mode="serve")
    plan_21 = ExecutionPlan(mesh=make_mesh((2, 1), ("data", "model")),
                            mode="serve")

    host = init_params(cfg, jax.random.PRNGKey(0))
    placed = learner_plan.device_put_params(cfg, host)
    store = PolicyStore()
    stats = publish_params(store, 0, learner_plan, cfg, placed)
    v, blob = store.fetch()
    manifest = Manifest.from_json(blob)

    legacy = load_pytree(save_pytree(
        learner_plan.host_gather(placed)), host)

    def check_parity(tree):
        for a, b in zip(jax.tree_util.tree_leaves(legacy),
                        jax.tree_util.tree_leaves(tree)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    sub = ChunkSubscriber(store)
    # sampler synced on the (smaller) 1x2 plan == whole-blob fetch
    v, tree, ss = sub.sync(host, cfg=cfg, plan=plan_12)
    check_parity(tree)
    placed_12 = plan_12.device_put_params(cfg, tree)
    check_parity(placed_12)
    # elastic re-fit: the cached version lands on *changed* plans
    for refit_plan in (plan_21, None):
        before = sub.chunks_fetched
        v2, tree2, ss2 = sub.sync(host, cfg=cfg,
                                  plan=refit_plan) if refit_plan \\
            else sub.sync(host, cfg=cfg)
        assert sub.chunks_fetched == before, "re-fit must not refetch"
        check_parity(tree2)
        if refit_plan is not None:
            check_parity(refit_plan.device_put_params(cfg, tree2))
    # plan-scoped: one host of the sampler mesh needs a strict subset
    need = sub.needed_refs(manifest, plan=plan_12, cfg=cfg,
                           devices=[plan_12.mesh.devices[0, 0]])
    scoped = {r.hash for _, refs in need for r in refs}
    full = manifest.hashes()
    assert scoped < full, (len(scoped), len(full))
    assert sub.chunks_fetched < manifest.num_entries
    print(json.dumps({"ok": True, "chunks": manifest.num_chunks,
                      "entries": manifest.num_entries,
                      "scoped": len(scoped), "hashes": len(full),
                      "egress": stats.max_host_egress,
                      "payload": stats.payload_bytes}))
""")


class TestElasticRefitParity:
    def test_refit_parity_on_debug_mesh(self):
        env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
        env.pop("XLA_FLAGS", None)
        out = subprocess.run([sys.executable, "-c", SUBPROC],
                             capture_output=True, text=True, env=env,
                             timeout=420)
        assert out.returncode == 0, out.stderr[-4000:]
        rec = json.loads(out.stdout.strip().splitlines()[-1])
        assert rec["ok"]
        # per-shard publish cut the worst host upload below a full copy
        assert rec["egress"] < rec["payload"]
        assert rec["scoped"] < rec["hashes"] <= rec["chunks"] \
            < rec["entries"]
