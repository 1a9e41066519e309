"""Learner and Sampler nodes of the HeteroRL star topology (§4.1, Fig. 3).

- Sampler nodes continuously generate rollout groups with their (stale)
  policy copy, score them locally (App. F localized rewards — group
  statistics never cross the network), and stream version-stamped batches
  to the learner.
- The learner consumes batches in arrival order inside a fixed
  time-window / staleness-window, updates parameters, and periodically
  publishes checkpoints to the ``PolicyStore``; samplers pull the latest
  version only after their simulated WAN delay D_M.
"""
from __future__ import annotations

import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.checkpoint import PolicyStore
from repro.config import (HeteroConfig, ModelConfig, RLConfig, ServeConfig,
                          TrainConfig)
from repro.core.diagnostics import MetricsHistory
from repro.data import PromptPipeline, score_rollouts
from repro.data.tasks import ArithmeticTask, Tokenizer
from repro.hetero.latency import sync_delay_s
from repro.parallel import ExecutionPlan, plan_from_flag
from repro.sampling import (ContinuousEngine, build_engine,
                            rollout_from_results, token_logps)
from repro.serving.api import Request, SamplingParams
from repro.training import TrainState, jit_train_step, optimizer_of
from repro.transport import ChunkSubscriber, SimulatedLink, publish_params


@dataclasses.dataclass
class RolloutBatch:
    tokens: np.ndarray          # (B, T)
    mask: np.ndarray            # (B, T-1) target-position mask
    sampler_lp: np.ndarray      # (B, T-1)
    rewards: np.ndarray         # (B,) group-contiguous
    version: int                # policy version that generated it
    created_s: float
    sampler_id: int

    def nbytes(self) -> int:
        return (self.tokens.nbytes + self.mask.nbytes
                + self.sampler_lp.nbytes + self.rewards.nbytes)


class SamplerNode:
    """Generates rollouts with a possibly-stale policy copy."""

    def __init__(self, sid: int, cfg: ModelConfig, rl: RLConfig,
                 pipeline: PromptPipeline, task: ArithmeticTask,
                 tok: Tokenizer, params: Any, store: PolicyStore,
                 hcfg: HeteroConfig, seed: int,
                 engine: Optional[str] = None,
                 logprob_impl: str = "fused",
                 plan: Optional[ExecutionPlan] = None,
                 serve: Optional[ServeConfig] = None,
                 spec_k: Optional[int] = None) -> None:
        self.sid = sid
        self.cfg, self.rl = cfg, rl
        self.pipeline, self.task, self.tok = pipeline, task, tok
        # serve-mode execution plan of this node (defaults to the
        # HeteroConfig.sampler_mesh knob). The node owns a *copy* of the
        # params placed on its plan: the learner's sharded step donates
        # its buffers, so a by-reference alias would die under it.
        self.plan = plan or plan_from_flag(hcfg.sampler_mesh, "serve")
        self.params = self.plan.device_put_params(cfg, params, copy=True)
        self.store = store
        self.hcfg = hcfg
        # shard-streamed checkpoint client: chunk cache + WAN link of this
        # node (repro.transport) — syncs move only the chunks this node's
        # plan needs whose content changed since the last sync
        self.link = SimulatedLink(
            bandwidth_mbps=getattr(hcfg, "bandwidth_mbps", float("inf")))
        self.subscriber = ChunkSubscriber(store, self.link)
        self.engine = engine or rl.engine
        # sampler nodes serve through the same request-level Engine API
        # as the front door: one engine instance per node, built lazily
        # at the first batch (its KV budget needs the prompt width) from
        # a ServeConfig — an explicit one, or a default sized to the
        # pipeline's rollout shape
        self.serve_cfg = serve
        # speculative decoding opt-in (explicit arg beats the HeteroConfig
        # knob): hetero samplers are exactly the GEPO setting spec decode
        # targets — tokens drafted against a stale policy are verified by
        # the *current* local policy, so accepted tokens carry its logps
        # and the importance-weight contract is untouched. Applied to the
        # default ServeConfig below; an explicit `serve` keeps its own.
        self.spec_k = hcfg.spec_k if spec_k is None else spec_k
        self._gen_engine = None
        self._engine_tp = -1
        # backend of the App. B.1 recompute — follows the learner's
        # TrainConfig.logprob_impl so A/B runs switch both halves
        self.logprob_impl = logprob_impl
        self.version = 0
        self.rng = np.random.default_rng(seed)
        self.key = jax.random.PRNGKey(seed)
        # instances cross threads in the threaded runtime: the node's
        # sampler thread mutates generation/sync state while the main
        # thread reads telemetry and drives elastic re-fits (RA005)
        self._lock = threading.Lock()
        self.batches_generated = 0
        self.syncs = 0
        # operator telemetry: generation rate of this node (the service
        # rate of the rollout queue in the HeteroRL picture) plus the
        # last rollout's engine stats, exposed via tokens_per_s below.
        # The first generate call pays jit compilation; it is accounted
        # separately (warmup_*) so tokens_per_s reports the steady-state
        # rate — the same convention as benchmarks/serve_throughput.py,
        # which warms executables outside the timed region.
        self.tokens_generated = 0
        self.gen_seconds = 0.0
        self.warmup_tokens = 0
        self.warmup_seconds = 0.0
        self.engine_stats: Dict[str, float] = {}
        # unified observability: this node's trace track + per-sampler
        # metric handles (Fig. 4/5 live quantities land here too, set by
        # the learner when it trains on this node's batches)
        self._track = f"sampler-{sid}"
        m = obs.metrics
        self._m_batches = m.counter(
            "sampler_batches_total", "rollout batches generated",
            sampler=sid)
        self._m_gen_tokens = m.counter(
            "sampler_gen_tokens_total", "completion tokens generated",
            sampler=sid)
        self._m_syncs = m.counter(
            "sampler_syncs_total", "weight syncs applied", sampler=sid)
        self._m_sync_bytes = m.counter(
            "sampler_sync_bytes_total", "weight-sync bytes on the wire",
            sampler=sid)
        self._g_version = m.gauge(
            "sampler_policy_version", "policy version this node holds",
            sampler=sid)
        self._g_accept = m.gauge(
            "sampler_accept_rate",
            "speculative-decode draft acceptance rate of this node",
            sampler=sid)
        self._m_drafted = m.counter(
            "sampler_drafted_tokens_total",
            "draft tokens proposed by this node's engine", sampler=sid)
        self._drafted_seen = 0   # engine stats are cumulative; counter
        #                          ingests per-batch deltas

    @property
    def tokens_per_s(self) -> float:
        """Steady-state generation rate (first-call compile excluded);
        falls back to the warmup-inclusive rate until a second batch has
        been generated."""
        if self.gen_seconds:
            return self.tokens_generated / self.gen_seconds
        if self.warmup_seconds:
            return self.warmup_tokens / self.warmup_seconds
        return 0.0

    def _engine_for(self, tp: int, b: int):
        """The node's engine, built on first use (the paged pool's budget
        needs the prompt width). Rebuilt only if the rollout shape
        changes."""
        with self._lock:
            if self._gen_engine is None or self._engine_tp != tp:
                serve = self.serve_cfg or ServeConfig(
                    engine=self.engine,
                    max_total_tokens=tp + self.rl.max_new_tokens,
                    num_slots=min(b, 8), spec_k=self.spec_k)
                if serve.max_total_tokens < tp + self.rl.max_new_tokens:
                    raise ValueError(
                        f"ServeConfig.max_total_tokens="
                        f"{serve.max_total_tokens} < prompt width {tp} "
                        f"+ max_new {self.rl.max_new_tokens}")
                self._gen_engine = build_engine(
                    self.cfg, self.params, serve, rl=self.rl,
                    vocab_limit=self.tok.vocab_size, plan=self.plan,
                    key=self.key)
                self._engine_tp = tp
            return self._gen_engine

    def generate_batch(self, now_s: float) -> RolloutBatch:
        req = self.pipeline.next_batch()
        prompts_np = np.asarray(req.prompts)
        prompts = jnp.asarray(prompts_np)
        b, tp = prompts_np.shape
        engine = self._engine_for(tp, b)
        with self._lock:
            self.key, k = jax.random.split(self.key)
        t0 = time.perf_counter()
        # rid = batch row, fresh key per batch: draws are bit-identical to
        # the legacy generate() path on either engine
        sp = SamplingParams.from_rl(self.rl)
        with obs.trace.span("sampler_generate", track=self._track,
                            sampler=self.sid, version=self.version,
                            batch=b):
            results = engine.generate(
                [Request(rid=r, prompt=prompts_np[r], params=sp)
                 for r in range(b)], key=k)
        roll = rollout_from_results(prompts_np, results,
                                    self.rl.max_new_tokens)
        if isinstance(engine, ContinuousEngine):
            roll["stats"] = engine.stats()
        ntok = int(np.asarray(roll["comp_mask"]).sum())
        dt = time.perf_counter() - t0
        with self._lock:
            if self.batches_generated == 0:     # jit compile folded in
                self.warmup_tokens += ntok
                self.warmup_seconds += dt
            else:
                self.tokens_generated += ntok
                self.gen_seconds += dt
            if "stats" in roll:
                self.engine_stats = dict(roll["stats"])
            if self.spec_k > 0 and self.engine_stats:
                self._g_accept.set(
                    self.engine_stats.get("accept_rate", 0.0))
                drafted = int(
                    self.engine_stats.get("drafted_tokens_total", 0))
                self._m_drafted.inc(drafted - self._drafted_seen)
                self._drafted_seen = drafted
        rewards = score_rollouts(self.task, self.tok, req.problems,
                                 np.asarray(roll["completions"]),
                                 req.group_size)
        b, tp = prompts.shape
        if self.rl.recompute_sampler_logps:
            # App. B.1: engine logps are untrusted; do a dedicated
            # forward pass under the *sampler's own* parameters.
            lp = token_logps(self.cfg, self.params, roll["tokens"],
                             logprob_impl=self.logprob_impl)
            comp_lp = lp[:, tp - 1:]
        else:
            comp_lp = roll["sampler_lp"]
        zeros = np.zeros((b, tp - 1), np.float32)
        mask = np.concatenate([zeros, np.asarray(roll["comp_mask"])], axis=1)
        sampler_lp = np.concatenate([zeros, np.asarray(comp_lp)], axis=1)
        with self._lock:
            self.batches_generated += 1
        self._m_batches.inc()
        self._m_gen_tokens.inc(ntok)
        return RolloutBatch(tokens=np.asarray(roll["tokens"]), mask=mask,
                            sampler_lp=sampler_lp, rewards=rewards,
                            version=self.version, created_s=now_s,
                            sampler_id=self.sid)

    def sync(self, plan: Optional[ExecutionPlan] = None) -> int:
        """Fetch the newest published checkpoint through the chunk
        transport (delta-synced against this node's local cache) and
        place it onto this node's execution plan. Returns the simulated
        bytes that moved on the wire (manifest + missing chunks), which
        feeds the payload-aware delay of the *next* sync.

        ``plan`` re-fits onto a changed ``ExecutionPlan`` (elastic sampler
        mesh: device loss/gain mid-run) — cached chunks are re-assembled
        and placed on the new shard grid, so an unchanged version re-fits
        without moving chunk bytes."""
        refit = plan is not None and plan != self.plan
        latest = self.store.latest_version()
        if latest < 0 or (latest <= self.version and not refit):
            if refit:
                # nothing (newer) published: re-place the live params so
                # plan and placement never disagree
                with self._lock:
                    self.plan = plan
                    self.params = self.plan.device_put_params(
                        self.cfg, self.params, copy=True)
                    self._push_params_locked()
            return 0
        # fetch against the *target* plan but commit it to self only
        # after the transport succeeds: if every retry raises, plan and
        # param placement must both stay on the old mesh (a half-applied
        # refit would make the next sync's refit check a false negative)
        target = plan if refit else self.plan
        for attempt in range(3):
            try:
                with obs.trace.span("weight_sync", track=self._track,
                                    sampler=self.sid, refit=refit):
                    v, host_tree, stats = self.subscriber.sync(
                        self.params, cfg=self.cfg, plan=target)
                break
            except KeyError:
                # threaded runtime race: the publisher pruned the fetched
                # manifest's chunks between fetch and snapshot — retry
                # against the newest version (bounded; chunks of a
                # retained manifest are pinned against GC)
                if attempt == 2:
                    raise
        with self._lock:
            if refit:
                self.plan = target
            if v > self.version or refit:
                self.params = self.plan.device_put_params(self.cfg,
                                                          host_tree)
                self._push_params_locked()
                if v > self.version:
                    self.version = v
                    self.syncs += 1
        self._m_syncs.inc()
        self._m_sync_bytes.inc(stats.bytes_on_wire)
        self._g_version.set(self.version)
        return stats.bytes_on_wire

    def _push_params_locked(self) -> None:
        """Keep the node's engine serving the freshly synced weights —
        the sampler-side half of the weight-sync contract. Caller holds
        ``self._lock``."""
        if self._gen_engine is not None:
            self._gen_engine.update_params(self.params)
            # elastic refit: the engine's jitted steps take the plan as a
            # static argument, so it must track the node's current plan
            self._gen_engine.plan = self.plan

    def next_delay(self, payload_bytes: int = 0) -> float:
        return sync_delay_s(self.rng, self.hcfg, payload_bytes)

    def link_stats(self) -> Dict[str, float]:
        """Per-node link telemetry: bytes on wire, dedup ratio (needed
        refs served from cache), simulated serialization seconds."""
        sub = self.subscriber
        total = sub.chunks_fetched + sub.chunk_hits
        row = {"sampler": float(self.sid), "syncs": float(self.syncs),
               "bytes_on_wire": float(self.link.bytes_on_wire),
               "sync_seconds": float(self.link.seconds),
               "chunks_fetched": float(sub.chunks_fetched),
               "chunk_hits": float(sub.chunk_hits),
               "dedup_ratio": sub.chunk_hits / total if total else 0.0}
        # thin view over the registry: the same row lands as per-sampler
        # link_* gauges so /metrics and sync_telemetry never disagree
        if obs.metrics.enabled:
            obs.metrics.set_many(
                "link", {k: v for k, v in row.items() if k != "sampler"},
                sampler=self.sid)
        return row


def link_telemetry(samplers: List[SamplerNode],
                   learner: LearnerNode) -> List[Dict[str, float]]:
    """Per-sampler weight-transport telemetry (bytes on wire, dedup
    ratio, simulated sync seconds) plus the learner's publish-side stream
    accounting as a pseudo-row (sampler=-1) — the one construction site
    both hetero runtimes report from."""
    rows = [s.link_stats() for s in samplers]
    rows.append({"sampler": -1.0,
                 "syncs": float(learner.step),
                 "bytes_on_wire": float(learner.bytes_streamed),
                 "sync_seconds": 0.0,
                 "chunks_fetched": float(learner.chunks_streamed),
                 "chunk_hits": 0.0, "dedup_ratio": 0.0})
    return rows


class LearnerNode:
    """Consumes rollout batches in arrival order within the staleness
    window; publishes checkpoints."""

    def __init__(self, cfg: ModelConfig, rl: RLConfig, tc: TrainConfig,
                 hcfg: HeteroConfig, state: TrainState,
                 store: PolicyStore,
                 plan: Optional[ExecutionPlan] = None) -> None:
        self.cfg, self.rl, self.tc, self.hcfg = cfg, rl, tc, hcfg
        # learner execution plan (defaults to the TrainConfig.mesh knob).
        # The sharded step donates the TrainState, so the node takes a
        # plan-placed *copy*: the caller's state (often a warm start
        # shared across runs) stays alive. The optimizer is the one the
        # state was built for.
        self.plan = plan or plan_from_flag(tc.mesh, "train")
        optimizer = optimizer_of(state)
        self.state = self.plan.device_put_state(cfg, state, optimizer,
                                                copy=True)
        self.store = store
        self.step_fn = jit_train_step(cfg, rl, tc, optimizer=optimizer,
                                      plan=self.plan)
        self.buffer: List[Tuple[float, RolloutBatch]] = []
        self.step = 0
        self.discarded = 0
        self.history = MetricsHistory()
        self.batch_shape: Optional[Tuple[int, int]] = None  # last tokens
        # cumulative publish telemetry (net-new bytes/chunks streamed)
        self.bytes_streamed = 0
        self.chunks_streamed = 0
        self.publish_stats = None
        self._publish()

    def _publish(self) -> None:
        """Stream this step's params into the store as per-shard,
        content-addressed chunks (repro.transport) — each shard's host
        view is pulled device-locally, no full host-gather — plus the
        version manifest. Unchanged chunks cost nothing."""
        self.publish_stats = publish_params(
            self.store, self.step, self.plan, self.cfg, self.state.params)
        self.bytes_streamed += self.publish_stats.bytes_new
        self.chunks_streamed += self.publish_stats.chunks_new

    def receive(self, now_s: float, batch: RolloutBatch) -> None:
        self.buffer.append((now_s, batch))

    def pop_eligible(self, now_s: float) -> Optional[RolloutBatch]:
        """Oldest-arrival batch satisfying window + staleness limits."""
        while self.buffer:
            arrival, batch = self.buffer[0]
            window_ok = (now_s - batch.created_s) <= self.hcfg.window_s
            stale_ok = (self.step - batch.version) <= self.hcfg.max_delay_steps
            if window_ok and stale_ok:
                self.buffer.pop(0)
                return batch
            self.buffer.pop(0)
            self.discarded += 1
        return None

    def train_on(self, batch: RolloutBatch) -> Dict[str, float]:
        with obs.trace.span("learner_step", track="learner",
                            step=self.step, version=batch.version,
                            sampler=batch.sampler_id):
            jb = self.plan.device_put_batch(self.cfg, {
                "tokens": jnp.asarray(batch.tokens),
                "mask": jnp.asarray(batch.mask),
                "sampler_lp": jnp.asarray(batch.sampler_lp),
                "rewards": jnp.asarray(batch.rewards)})
            self.batch_shape = tuple(batch.tokens.shape)
            t0 = time.perf_counter()
            self.state, metrics = self.step_fn(self.state, jb)
            jax.block_until_ready(self.state)
            out = {k: float(v) for k, v in metrics.items()}
            # host clock around the finished step (compile included on
            # a new batch shape)
            out["step_s"] = time.perf_counter() - t0
            self.step += 1
        out["staleness"] = float(self.step - 1 - batch.version)
        out["buffer_len"] = float(len(self.buffer))
        self.history.append(self.step, out)
        # per-step fan-in to the unified registry: every scalar becomes a
        # learner_* gauge, and the paper's Fig. 4/5 stability quantities
        # additionally land as per-sampler gauges (the sampler whose
        # batch this step consumed) — live staleness / KL / IW-variance
        if obs.metrics.enabled:
            obs.metrics.set_many("learner", out)
            obs.metrics.gauge("learner_steps_total").set(self.step)
            for k in ("staleness", "kl", "iw_var"):
                if k in out:
                    obs.metrics.gauge(
                        f"sampler_{k}",
                        f"{k} of the last batch trained from this sampler",
                        sampler=batch.sampler_id).set(out[k])
        if self.step % self.hcfg.sync_interval_steps == 0:
            with obs.trace.span("publish_checkpoint", track="learner",
                                step=self.step):
                self._publish()
        return out
