"""Serving driver: what a HeteroRL *sampler node* runs. CPU-scale by
default (smoke config); ``--full-width`` serves the arch's published
config (random-init weights), and ``dryrun.py`` lowers the full-size
serving shapes (prefill_32k / decode_32k / long_500k).

All deployment knobs live in one ``ServeConfig`` (engine kind, slots,
page size, decode horizon, pool size, mesh, admission limits) — the
flags below map 1:1 onto its fields and the same object drives the
request-level engine API, the asyncio front door, and HeteroRL sampler
nodes.

Batch mode (default) runs ``--rounds`` batches through the engine:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b \
      --batch 16 --max-new 24 --engine continuous --slots 8

Front-door mode serves HTTP + websocket with admission control and SLO
telemetry (POST /generate, GET /ws, /healthz, /metrics):
  PYTHONPATH=src python -m repro.launch.serve --listen --port 8100

Tensor-parallel serving runs through the same ExecutionPlan as training
(on CPU export the host-device override first):
  XLA_FLAGS=--xla_force_host_platform_device_count=4 \
  PYTHONPATH=src python -m repro.launch.serve --mesh 1x4
"""
from __future__ import annotations

import argparse
import time
from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro import obs
from repro.compile_cache import enable_compile_cache
from repro.config import ModelConfig, RLConfig, ServeConfig
from repro.configs import config_for
from repro.data import ArithmeticTask, Tokenizer, encode_prompts
from repro.models import encode
from repro.parallel import ExecutionPlan, plan_from_flag
from repro.sampling import build_engine
from repro.serving.api import Request, SamplingParams

PROMPT_WIDTH = 8                     # ArithmeticTask prompt width


def parse_serve_config(args: argparse.Namespace) -> ServeConfig:
    """The single deployment object the loose flags collapse into."""
    return ServeConfig(
        engine=args.engine, num_slots=args.slots, page_size=args.page_size,
        prefill_chunk=args.prefill_chunk, sync_every=args.sync_every,
        max_total_tokens=args.max_total_tokens
        or args.prompt_width + args.max_new,
        num_pages=args.num_pages, prefix_cache=not args.no_prefix_cache,
        mesh=args.mesh, paged_attn_impl=args.paged_attn_impl,
        host=args.host, port=args.port, max_queue=args.max_queue,
        default_deadline_s=args.deadline_s, seed=args.seed,
        spec_k=args.spec_k, spec_ngram_max=args.spec_ngram,
        spec_rescore=not args.no_spec_rescore)


class Deployment(NamedTuple):
    """What the flags resolve to: the model, its random-init params placed
    on the serve plan, and the sampling profile."""
    cfg: ModelConfig
    serve: ServeConfig
    rl: RLConfig
    plan: ExecutionPlan
    params: Any
    memory: Optional[jax.Array]
    key: jax.Array


def parse_args(argv: Optional[Sequence[str]] = None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--full-width", action="store_true",
                    help="the arch's published config instead of its "
                         "smoke-sized variant")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--rounds", type=int, default=3)
    # ServeConfig fields ---------------------------------------------------
    ap.add_argument("--engine", choices=("static", "continuous"),
                    default="static")
    ap.add_argument("--slots", type=int, default=8)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=0,
                    help="prompt tokens prefilled per engine iteration "
                         "(0 = whole prompt in one chunk)")
    ap.add_argument("--sync-every", type=int, default=8)
    ap.add_argument("--max-total-tokens", type=int, default=0,
                    help="per-request prompt+completion cap "
                         "(0 = prompt width + --max-new)")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="KV page-pool override (0 = full budget per slot)")
    ap.add_argument("--no-prefix-cache", action="store_true",
                    help="disable shared-prefix KV page reuse")
    ap.add_argument("--mesh", default="1x1",
                    help="serve mesh DxM (batch over data × tensor "
                         "parallel over model)")
    ap.add_argument("--paged-attn-impl", default=None,
                    choices=("auto", "pallas", "ref", "gather"))
    ap.add_argument("--spec-k", type=int, default=0,
                    help="speculative decoding: drafts per verification "
                         "round (0 = off; continuous engine only)")
    ap.add_argument("--spec-ngram", type=int, default=3,
                    help="longest suffix n-gram the prompt-lookup "
                         "drafter matches")
    ap.add_argument("--no-spec-rescore", action="store_true",
                    help="skip the fused-layers acceptance rescore "
                         "(drops the drift gauge, saves one launch/round)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8100)
    ap.add_argument("--max-queue", type=int, default=256)
    ap.add_argument("--deadline-s", type=float, default=0.0,
                    help="default TTFT deadline applied to front-door "
                         "requests (0 = none)")
    # sampling profile -----------------------------------------------------
    ap.add_argument("--temperature", type=float, default=0.6)
    ap.add_argument("--top-k", type=int, default=20)
    ap.add_argument("--top-p", type=float, default=0.95)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--listen", action="store_true",
                    help="run the HTTP/websocket front door instead of "
                         "batch rounds")
    # observability --------------------------------------------------------
    ap.add_argument("--obs", action="store_true",
                    help="enable the unified metrics registry + span "
                         "tracer (off by default: zero-cost)")
    ap.add_argument("--trace-out", default="",
                    help="write a Chrome-trace/Perfetto JSON here on exit "
                         "(implies --obs)")
    args = ap.parse_args(argv)
    args.prompt_width = PROMPT_WIDTH
    return args


def load(args: argparse.Namespace) -> Deployment:
    cfg = config_for(args.arch, args.full_width)
    serve = parse_serve_config(args)
    rl = RLConfig(temperature=args.temperature, top_k=args.top_k,
                  top_p=args.top_p, max_new_tokens=args.max_new,
                  engine=serve.engine)
    plan = plan_from_flag(serve.mesh, "serve")
    key = jax.random.PRNGKey(serve.seed)
    params = plan.init_params(cfg, key)

    memory = None
    if cfg.is_encdec:
        frames = jax.random.normal(key, (args.batch, cfg.encoder_seq,
                                         cfg.d_model), jnp.float32)
        memory = encode(cfg, params, frames.astype(cfg.dtype))
    elif cfg.memory_seq:
        memory = 0.02 * jax.random.normal(
            key, (args.batch, cfg.memory_seq, cfg.d_model)
        ).astype(cfg.dtype)
    return Deployment(cfg, serve, rl, plan, params, memory, key)


def make_task(seed: int) -> ArithmeticTask:
    return ArithmeticTask(max_operand=99, ops="+-", prompt_width=PROMPT_WIDTH,
                          seed=seed)


def make_requests(task: ArithmeticTask, tok: Tokenizer, n: int,
                  params: SamplingParams, rid0: int = 0
                  ) -> Tuple[list, List[Request]]:
    """``n`` fresh problems and their requests, ids from ``rid0``."""
    probs = task.sample_batch(n)
    prompts = encode_prompts(tok, probs)
    return probs, [Request(rid=rid0 + i, prompt=row, params=params)
                   for i, row in enumerate(prompts)]


def main(argv: Optional[Sequence[str]] = None) -> None:
    enable_compile_cache()
    args = parse_args(argv)
    if args.obs or args.trace_out:
        obs.configure(True)

    cfg, serve, rl, plan, params, memory, key = load(args)
    print(f"[serve] {plan.describe()}")
    tok = Tokenizer()
    task = make_task(serve.seed)

    if args.listen:
        import asyncio

        from repro.serving.server import serve_forever
        if memory is not None:
            raise SystemExit("--listen serves decoder-only KV-cache "
                             "architectures (continuous engine)")
        try:
            asyncio.run(serve_forever(cfg, params, serve, rl=rl,
                                      tokenizer=tok,
                                      vocab_limit=tok.vocab_size, plan=plan,
                                      key=key))
        finally:
            if args.trace_out:
                n = obs.export_chrome_trace(args.trace_out)
                print(f"[serve] wrote {n} trace events -> {args.trace_out}")
        return

    engine = build_engine(cfg, params, serve, rl=rl,
                          vocab_limit=tok.vocab_size, memory=memory,
                          plan=plan, key=key)
    sp = SamplingParams.from_rl(rl)
    total_tok, rid = 0, 0
    t0 = time.time()
    for r in range(args.rounds):
        probs, reqs = make_requests(task, tok, args.batch, sp, rid)
        rid += len(reqs)
        key, k = jax.random.split(key)
        t1 = time.time()
        results = engine.generate(reqs, key=k)
        dt = time.time() - t1
        n_tok = sum(res.gen_count for res in results)
        total_tok += n_tok
        outs = [tok.decode(res.tokens) for res in results]
        util = ""
        if hasattr(engine, "stats"):
            st = engine.stats()
            util = (f" | slot-util {st['slot_utilization']:.2f}"
                    f" ({st['decode_steps']} decode steps)")
        print(f"[serve] round {r}: {n_tok} tokens in {dt:.2f}s "
              f"({n_tok/dt:.1f} tok/s){util} | sample: "
              f"{probs[0].prompt.strip()!r} -> {outs[0]!r}")
    print(f"[serve] arch={cfg.name} engine={serve.engine} "
          f"batch={args.batch} total {total_tok} tokens, "
          f"{total_tok/(time.time()-t0):.1f} tok/s incl. compile")
    if args.trace_out:
        n = obs.export_chrome_trace(args.trace_out)
        print(f"[serve] wrote {n} trace events -> {args.trace_out}")


if __name__ == "__main__":
    main()
