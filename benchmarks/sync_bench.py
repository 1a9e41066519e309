"""Weight-transport A/B: whole-blob npz sync vs chunked content-addressed
delta sync (repro.transport), in bytes on the wire and simulated sync
seconds.

    PYTHONPATH=src python -m benchmarks.sync_bench [--smoke]

Part A (any device count) replays a publish/sync series where part of the
model is frozen (embeddings + head — a standard RL-tuning setting): the
whole-blob path re-ships the full npz every sync, the chunked path moves
only the changed chunks, across sampler sync cadences (sync every k-th
publish).

Part B needs a >=4-device mesh (in-process when visible, e.g. under the
CI multidevice job's forced host devices; otherwise a subprocess forces
8) and checks the sharded claims end-to-end with real nodes: a
``SamplerNode`` on a *smaller* plan (1x2 serve) synced from a 2x2 train
learner gets params byte-identical to the legacy whole-blob fetch, its
fetch is a strict subset of the learner's per-shard chunk entries (and a
host-scoped subscriber a strict subset of the distinct chunks), and an
elastic re-fit onto a changed plan lands the same bytes without moving
new chunks.

CSV: sync,setting,metrics...
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
from typing import List

SMOKE = os.environ.get("BENCH_SMOKE", "0") == "1"

BANDWIDTH_MBPS = 100.0


def _tiny():
    from repro.config import ModelConfig, ATTN, MLP
    return ModelConfig(name="sync-lm", family="dense", num_layers=2,
                       d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                       vocab_size=64, block_pattern=(ATTN,),
                       ffn_pattern=(MLP,), dtype="float32",
                       attn_impl="naive", remat=False, rope_theta=1e4)


def _perturbed(params, step: int):
    """Simulated training step that leaves embed/lm_head/final_norm
    frozen (chunked sync should skip them; whole-blob cannot)."""
    import jax
    from repro.checkpoint.store import path_key

    frozen = ("embed", "lm_head", "final_norm")

    def bump(path, leaf):
        if path_key(path).split("/")[-1] in frozen or path_key(path) in frozen:
            return leaf
        return leaf + 1e-3 * (step + 1)

    return jax.tree_util.tree_map_with_path(bump, params)


def _series_rows() -> List[str]:
    import jax
    import numpy as np

    from repro.checkpoint import PolicyStore, load_pytree, save_pytree
    from repro.config import HeteroConfig
    from repro.hetero.latency import sync_delay_s
    from repro.models import init_params
    from repro.parallel import local_plan
    from repro.transport import ChunkSubscriber, SimulatedLink, publish_params

    cfg = _tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    plan = local_plan("train")
    n_publishes = 4 if SMOKE else 8
    # the propagation term of sync_delay_s is identical for both paths, so
    # the seconds columns report the serialization term only — the part
    # the payload size actually controls at BANDWIDTH_MBPS
    hcfg = HeteroConfig(delay_distribution="constant", delay_min_s=0.0,
                        delay_median_s=0.0, bandwidth_mbps=BANDWIDTH_MBPS)
    rng = np.random.default_rng(0)

    rows = []
    for cadence in (1, 2, 4):
        store = PolicyStore()
        link = SimulatedLink(bandwidth_mbps=BANDWIDTH_MBPS)
        sub = ChunkSubscriber(store, link)
        blob_bytes = 0
        blob_seconds = 0.0
        chunk_seconds = 0.0
        p = params
        publish_stats = []
        for v in range(n_publishes):
            # the sampler joins at v0 (cold cache, full fetch) and then
            # syncs every cadence-th publish — deltas against its cache
            p = _perturbed(p, v) if v else p
            publish_stats.append(publish_params(store, v, plan, cfg, p))
            if v and v % cadence != cadence - 1:
                continue
            # chunked-delta sampler sync
            _, tree, ss = sub.sync(p, cfg=cfg, plan=local_plan("serve"))
            chunk_seconds += sync_delay_s(rng, hcfg, ss.bytes_on_wire)
            # legacy whole-blob sampler sync of the same version
            blob = save_pytree(p)
            blob_bytes += len(blob)
            blob_seconds += sync_delay_s(rng, hcfg, len(blob))
            # transport restore must stay byte-identical to the blob
            legacy = load_pytree(blob, p)
            for a, b in zip(jax.tree_util.tree_leaves(legacy),
                            jax.tree_util.tree_leaves(tree)):
                np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        chunk_bytes = link.bytes_on_wire
        assert chunk_bytes < blob_bytes, (
            f"chunked-delta sync must move strictly fewer bytes than "
            f"whole-blob on partially-unchanged publishes "
            f"({chunk_bytes} vs {blob_bytes})")
        stream_new = sum(s.bytes_new for s in publish_stats)
        stream_full = sum(s.payload_bytes for s in publish_stats)
        rows.append(
            f"sync,cadence={cadence},{blob_bytes},{chunk_bytes},"
            f"{chunk_bytes / blob_bytes:.3f},{blob_seconds:.2f},"
            f"{chunk_seconds:.2f},{stream_new}/{stream_full}")
    return (["sync,setting,blob_bytes,chunk_bytes,byte_ratio,"
             "blob_ser_s,chunk_ser_s,publish_new/full"] + rows)


def _mesh_rows() -> List[str]:
    """Sharded end-to-end checks on a 2x2 learner / 1x2 sampler; needs
    >= 4 visible devices (run under XLA_FLAGS host-device forcing)."""
    import jax
    import numpy as np

    from repro.checkpoint import PolicyStore, load_pytree, save_pytree
    from repro.config import HeteroConfig, RLConfig, TrainConfig
    from repro.data import ArithmeticTask, PromptPipeline, Tokenizer
    from repro.hetero.nodes import LearnerNode, SamplerNode
    from repro.models import init_params
    from repro.parallel import ExecutionPlan, make_debug_mesh, make_mesh
    from repro.training import init_state
    from repro.transport import ChunkSubscriber, Manifest

    cfg = _tiny()
    rl = RLConfig(loss_type="gepo", group_size=4, max_new_tokens=4,
                  temperature=1.0, top_k=0, top_p=1.0)
    tc = TrainConfig(learning_rate=1e-3, total_steps=8)
    hcfg = HeteroConfig(num_samplers=1, bandwidth_mbps=BANDWIDTH_MBPS)
    task = ArithmeticTask(max_operand=9, ops="+", prompt_width=5, seed=0)
    tok = Tokenizer()

    learner_plan = ExecutionPlan(mesh=make_debug_mesh(2, 2), mode="train")
    sampler_plan = ExecutionPlan(mesh=make_mesh((1, 2), ("data", "model")),
                                 mode="serve")
    state = init_state(cfg, tc, init_params(cfg, jax.random.PRNGKey(0)))
    store = PolicyStore()
    learner = LearnerNode(cfg, rl, tc, hcfg, state, store,
                          plan=learner_plan)   # publishes v0 in ctor
    pub = learner.publish_stats
    v, blob = store.fetch()
    manifest = Manifest.from_json(blob)

    # real sampler node on the smaller plan syncs through the transport
    sampler = SamplerNode(0, cfg, rl, PromptPipeline(task, tok, 4, 4),
                          task, tok, learner.state.params, store, hcfg,
                          seed=0, plan=sampler_plan)
    sampler.version = -1                       # force a fetch of v0
    moved = sampler.sync()
    # byte-identity vs the legacy whole-blob path
    host = learner.plan.host_gather(learner.state.params)
    legacy = load_pytree(save_pytree(host), host)
    for a, b in zip(jax.tree_util.tree_leaves(legacy),
                    jax.tree_util.tree_leaves(sampler.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # the sampler's fetch is a strict subset of the learner's per-shard
    # chunk entries (replica entries dedup onto content-addressed chunks)
    fetched = sampler.subscriber.chunks_fetched
    assert fetched <= manifest.num_chunks < manifest.num_entries, (
        fetched, manifest.num_chunks, manifest.num_entries)
    hashes = manifest.hashes()
    assert fetched < manifest.num_entries

    # one *host* of the sampler mesh (device column 0) needs a strict
    # subset of even the distinct chunks: model-sharded leaves contribute
    # only their first column
    scoped = ChunkSubscriber(store)
    need = scoped.needed_refs(manifest, plan=sampler_plan, cfg=cfg,
                              devices=[sampler_plan.mesh.devices[0, 0]])
    scoped_hashes = {r.hash for _, refs in need for r in refs}
    assert scoped_hashes < hashes, (len(scoped_hashes), len(hashes))

    # elastic re-fit: the same version lands on a *changed* plan from the
    # local cache (no new chunk bytes), byte-identical again
    refit_plan = ExecutionPlan(mesh=make_mesh((2, 1), ("data", "model")),
                               mode="serve")
    before = sampler.subscriber.chunks_fetched
    sampler.sync(plan=refit_plan)
    for a, b in zip(jax.tree_util.tree_leaves(legacy),
                    jax.tree_util.tree_leaves(sampler.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert sampler.subscriber.chunks_fetched == before, \
        "re-fit must come from the chunk cache, not the wire"
    assert sampler.params["embed"].sharding.mesh == refit_plan.mesh

    blob_bytes = len(save_pytree(host))
    return [
        "sync,setting,chunks,entries,hashes,fetched,scoped_hashes,"
        "payload_bytes,blob_bytes,max_host_egress,sampler_wire_bytes",
        f"sync,mesh_2x2_to_1x2,{manifest.num_chunks},"
        f"{manifest.num_entries},{len(hashes)},{fetched},"
        f"{len(scoped_hashes)},{manifest.payload_bytes},{blob_bytes},"
        f"{pub.max_host_egress},{moved}",
    ]


def run() -> List[str]:
    import jax
    rows = _series_rows()
    if len(jax.devices()) >= 4:
        rows += _mesh_rows()
    else:
        rows += _mesh_rows_subprocess()
    return rows


def _mesh_rows_subprocess() -> List[str]:
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    # a virtual-device rehearsal on the CPU: never a second process onto
    # an accelerator the parent may hold
    env = dict(
        os.environ, JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=8",
        PYTHONPATH=os.pathsep.join(
            [p for p in (os.environ.get("PYTHONPATH"),) if p]
            + [os.path.join(repo, "src"), repo]))
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.sync_bench", "--mesh-worker"],
        capture_output=True, text=True, env=env, timeout=420)
    if out.returncode != 0:
        raise RuntimeError(f"sync_bench mesh worker failed:\n"
                           f"{out.stderr[-2000:]}")
    return json.loads(out.stdout.strip().splitlines()[-1])


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh-worker", action="store_true",
                    help="internal: emit the mesh rows as JSON")
    args = ap.parse_args()
    if args.smoke:
        os.environ["BENCH_SMOKE"] = "1"
        global SMOKE
        SMOKE = True
    if args.mesh_worker:
        print(json.dumps(_mesh_rows()))
        return
    for r in run():
        print(r, flush=True)


if __name__ == "__main__":
    main()
