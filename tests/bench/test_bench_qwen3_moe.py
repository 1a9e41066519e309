"""The sparse-expert family (``bench/models/qwen3_moe.py``) against the
program, at a tiny size on the CPU, on seeded random weights: a layer that
holds a block of the experts gives that block's part of the reference's
MoE and the blocks add up to the uncut layer; the engine's paged prefill
and decode give the reference's logits; the learner's loss, gradients and
update follow the reference's; and the committed configuration's layout,
work counts and ``expert_ffn_roofline`` reader.

Tolerances. Program and reference both run in float32 here; on the CPU
a float32 matmul is exact to float32 rounding, so the two differ by the
order of summation alone (about 1e-7 relative at these sizes). The
limits below leave that a factor of 100 or more, and each sits under
what computing the experts in bfloat16 (8 mantissa bits, rounding of
about 4e-3 relative) gives, which the controls show."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny import ROOT, TINY_LIMITS, run_cell, tiny_traffic

from bench.lib import program, reference, spec, work
from bench.lib import trace as T
from bench.lib import weights as W

SEED = 2 ** 31 + 7717
E, HELD, K = 8, 2, 2

MATCHES = {"hidden_size": "d_model", "moe_intermediate_size": "d_ff",
           "num_hidden_layers": "num_layers",
           "num_attention_heads": "num_heads",
           "num_key_value_heads": "num_kv_heads", "head_dim": "head_dim",
           "vocab_size": "vocab_size", "tie_word_embeddings": "tie_embeddings",
           "router_experts": "num_experts", "num_experts": "num_held_experts",
           "first_held_expert": "first_held_expert",
           "num_experts_per_tok": "experts_per_token"}


def tiny_moe(first: int = 2, held: int = HELD) -> dict:
    """A tiny ``qwen3_moe`` configuration holding experts first..first+held-1
    of E, with the program's entry cut to match, in float32."""
    c = {"name": "tiny-qwen3-moe", "source": "test",
         "model_type": "qwen3_moe", "hidden_size": 64,
         "intermediate_size": 192, "moe_intermediate_size": 32,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "head_dim": 16, "num_experts": held,
         "router_experts": E, "first_held_expert": first,
         "num_experts_per_tok": K, "norm_topk_prob": True,
         "vocab_size": 512, "rope_theta": 1000000, "rms_norm_eps": 1e-06,
         "initializer_range": 0.02, "tie_word_embeddings": False,
         "torch_dtype": "float32", "reduced": []}
    c["program"] = {"arch": "qwen3-30b-a3b", "matches": MATCHES, "overrides": {
        "num_layers": 2, "d_model": 64, "d_ff": 32, "num_heads": 4,
        "num_kv_heads": 2, "head_dim": 16, "vocab_size": 512,
        "num_experts": E, "experts_per_token": K,
        "expert_block": [first, held], "dtype": "float32",
        "attn_chunk": 16}}
    c["learner"] = {"optimizer": "adafactor", "micro_batch_rows": 4,
                    "mesh": "1x1",
                    "train": {"learning_rate": 0.001, "warmup_frac": 0.0}}
    return c


def rel_err(a, b) -> float:
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.abs(a - b).max() / np.abs(b).max())


@pytest.fixture
def bf16_experts(monkeypatch):
    """The control: the program's experts computed in bfloat16 where the
    configuration states float32."""
    from repro.models import moe
    orig = moe.held_experts_ffn

    def low(xf, gate, ids, p, first, count):
        p16 = {n: a.astype(jnp.bfloat16) for n, a in p.items()}
        return orig(xf.astype(jnp.bfloat16), gate, ids, p16, first,
                    count).astype(xf.dtype)

    def install():
        monkeypatch.setattr(moe, "held_experts_ffn", low)
    return install


# --------------------------------------------------------------------------
# (a) the shares sum to the whole


def _share_outputs(h):
    """Program and reference outputs of the first layer's MoE on ``h`` for
    each held block in turn and for the uncut layer, every block's weights
    sliced from the uncut layer's."""
    from repro.models.moe import moe_ffn
    whole = tiny_moe(first=0, held=E)
    cfg_whole = program.model_config(whole)
    lw = {n: a[0] for n, a in W.make(whole, SEED, cfg_whole.padded_vocab
                                     ).items() if n in ("router", "w_gate",
                                                        "w_up", "w_down")}
    out = {}
    for first in [*range(0, E, HELD), None]:
        c = whole if first is None else tiny_moe(first)
        cut = slice(0, E) if first is None else slice(first, first + HELD)
        blk = {n: (a if n == "router" else a[cut]) for n, a in lw.items()}
        y, _ = moe_ffn(program.model_config(c), blk, h)
        ref = spec.family(c).experts(c, blk, h, "f32")
        out[first] = (y, ref)
    return out


def test_shares_sum_to_the_whole_layer():
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 64))
    out = _share_outputs(h)
    y_whole, ref_whole = out.pop(None)
    for first, (y, ref) in out.items():        # each block, its own part
        assert rel_err(y, ref) < 1e-5, first
    summed = sum(y for y, _ in out.values())
    assert rel_err(summed, y_whole) < 1e-5
    assert rel_err(summed, ref_whole) < 1e-5
    assert rel_err(y_whole, ref_whole) < 1e-5


def test_shares_in_bf16_are_not_the_reference(bf16_experts):
    bf16_experts()
    h = jax.random.normal(jax.random.PRNGKey(1), (3, 16, 64))
    out = _share_outputs(h)
    y_whole, ref_whole = out.pop(None)
    assert rel_err(y_whole, ref_whole) > 1e-3


# --------------------------------------------------------------------------
# (c) the engine's paged prefill and decode against the full forward


def _paged_logits(c, params, rows, prompt_lens):
    """Each row's prompt through the engine's prefill program, then the
    rest of the rows one token a step through the paged decode step,
    every row at its own position in one batch. Returns the logits at
    every position, (R, W, V_pad)."""
    from repro.models import decode_step
    from repro.sampling import continuous as cont
    from repro.sampling.paged_cache import init_paged_pool
    cfg = program.model_config(c)
    r, w = rows.shape
    page, per_row = 8, -(-w // 8)
    pool = init_paged_pool(cfg, 1 + r * per_row, page)
    table = 1 + np.arange(r * per_row, dtype=np.int32).reshape(r, per_row)
    logits = np.zeros((r, w, cfg.padded_vocab), np.float32)
    for i, p in enumerate(prompt_lens):
        lg, pool = cont._prefill_chunk_jit(cfg, params, pool,
                                           jnp.asarray(table[i:i + 1]),
                                           jnp.asarray(rows[i:i + 1, :p]),
                                           jnp.int32(0))
        logits[i, :p] = np.asarray(lg)
    step = jax.jit(lambda pool, tok, pos: decode_step(
        cfg, params, pool, tok, pos, page_table=jnp.asarray(table)))
    for t in range(min(prompt_lens), w):
        # a row still in its prompt decodes nothing: its position lies
        # past the table, where writes are dropped
        live = np.asarray(prompt_lens) <= t
        pos = np.where(live, t, per_row * page).astype(np.int32)
        lg, pool = step(pool, jnp.asarray(rows[:, t]), jnp.asarray(pos))
        logits[live, t] = np.asarray(lg)[live]
    return logits


def test_paged_prefill_and_decode_give_the_reference_logits():
    c = tiny_moe()
    cfg = program.model_config(c)
    params = program.make_program_weights(cfg, c, SEED)
    rows = np.random.default_rng(3).integers(3, 512, (3, 20)).astype(np.int32)
    got = _paged_logits(c, params, rows, [5, 9, 12])
    wts = W.make(c, SEED, cfg.padded_vocab)
    x, _ = reference.hidden(c, wts, jnp.asarray(rows), "f32")
    h = reference.rmsnorm(x, wts["final_norm"], c["rms_norm_eps"])
    want = jnp.matmul(h, reference.head_matrix(wts, c["vocab_size"]),
                      precision=reference.HI)
    # logits here are about 0.3 in size; float32 order-of-summation
    # differences reach about 1e-7 of that
    np.testing.assert_allclose(got[..., :c["vocab_size"]], np.asarray(want),
                               atol=2e-5, rtol=1e-4)


def test_rollout_cell_runs_correct_on_the_moe_family():
    cell = spec.Cell(name="tiny.rollout.moe", chips=1, config=tiny_moe(),
                     traffic=tiny_traffic("rollout"),
                     limits={k: TINY_LIMITS[k] for k in ("logp_gap",
                                                         "token_gap")},
                     end_to_end=[], per_layer=[])
    out = run_cell(cell, SEED)
    assert out["correct"], out["checks"]
    assert out["window_compiles"] == 0 and out["failed"] == 0


# --------------------------------------------------------------------------
# (d) the learner through the MoE layer


def learn_cell() -> spec.Cell:
    return spec.Cell(name="tiny.learn.moe", chips=1, config=tiny_moe(),
                     traffic=tiny_traffic("learn"),
                     limits={k: TINY_LIMITS[k] for k in ("loss_gap",
                                                         "grad_gap",
                                                         "update_gap")},
                     end_to_end=[], per_layer=[])


def test_learner_step_follows_the_reference():
    out = run_cell(learn_cell(), SEED)
    assert out["correct"], out["checks"]
    assert out["window_compiles"] == 0 and out["failed"] == 0


def test_learner_gradient_of_the_experts_matches_jax_grad():
    """One layer's MoE: the program's gradient with respect to the held
    experts' weights, the router and the input is the reference's
    ``jax.grad``."""
    from repro.models.moe import moe_ffn
    c = tiny_moe()
    cfg = program.model_config(c)
    wts = W.make(c, SEED, cfg.padded_vocab)
    p = {n: wts[n][0] for n in ("router", "w_gate", "w_up", "w_down")}
    h = jax.random.normal(jax.random.PRNGKey(2), (2, 12, 64))
    tgt = jax.random.normal(jax.random.PRNGKey(4), (2, 12, 64))

    def prog(p, h):
        return jnp.sum(moe_ffn(cfg, p, h)[0] * tgt)

    def ref(p, h):
        return jnp.sum(spec.family(c).experts(c, p, h, "f32") * tgt)

    gp = jax.grad(prog, argnums=(0, 1))(p, h)
    gr = jax.grad(ref, argnums=(0, 1))(p, h)
    for a, b in zip(jax.tree_util.tree_leaves(gp),
                    jax.tree_util.tree_leaves(gr)):
        assert rel_err(a, b) < 1e-5


# --------------------------------------------------------------------------
# (e) the committed configuration: layout, work counts, the reader


def committed(published: bool = False) -> dict:
    c = json.loads((ROOT / "bench" / "configs" / "qwen3-30b-a3b.json"
                    ).read_text())
    return dict(c, **c["published"]) if published else c


def test_committed_layout_matches_the_program():
    c = committed()
    cfg = program.model_config(c)
    program.check_layout(cfg, c)
    assert (cfg.num_experts, cfg.first_held_expert, cfg.num_held_experts,
            cfg.experts_per_token, cfg.d_ff) == (128, 0, 16, 8, 768)
    shapes = {n: a.shape for n, a in jax.eval_shape(
        lambda: W.make(c, 0, cfg.padded_vocab)).items()}
    assert shapes["router"] == (24, 2048, 128)
    assert shapes["w_gate"] == shapes["w_up"] == (24, 16, 2048, 768)
    assert shapes["w_down"] == (24, 16, 768, 2048)


def test_work_counts_by_hand():
    c = committed()
    attn = 2048 * (32 + 2 * 4) * 128 + 32 * 128 * 2048
    assert attn == 18_874_368
    expert = 3 * 2048 * 768
    # a token picks 8 of 128 experts: 8 x 16/128 = 1 held expert on average
    layer = attn + 2048 * 128 + expert
    assert work.matmul_params(c) == 24 * layer + 151_936 * 2048
    assert work.kv_bytes_per_token(c) == 24 * 2 * 4 * 128 * 2
    # one token touches one held expert on average; 64 touch 15.74 of 16
    norms = 49 * 2048
    dense = 24 * (attn + 2048 * 128) + 151_936 * 2048 + norms
    assert work.weight_bytes(c, 1) == pytest.approx(2 * (dense + 24 * expert))
    touched = 16 * (1 - (1 - 8 / 128) ** 64)
    assert touched == pytest.approx(15.742, abs=1e-3)
    assert work.weight_bytes(c, 64) == pytest.approx(
        2 * (dense + 24 * touched * expert))
    byts, flops = spec.family(c).expert_ffn_work(c, 64)
    # 64 tokens x 8 picks x 16/128 = 64 held pairs, each row in and out
    assert byts == pytest.approx(2 * 24 * (touched * expert + 64 * 2 * 2048))
    assert flops == pytest.approx(24 * 6 * 2048 * 768 * 64)
    # the experts at their roofline: 3.6 GB, about 4.4 ms a step at 819 GB/s
    assert byts / 819e9 == pytest.approx(4.4e-3, rel=0.02)


def test_expert_ffn_roofline_on_a_synthetic_trace():
    reader = spec.metric_reader("expert_ffn_roofline")
    # decode chunks [0, 1000) and [3000, 3500), a prefill [1500, 2000)
    dev = T.Device(
        ops=[("%ragged-dot-metadata custom-call", 10, 5),      # not a matmul
             ("%ragged-dot-none.1 custom-call", 20, 200),
             ("%ragged-dot-none.2 custom-call", 300, 100),
             ("%fusion.7 fusion", 500, 300),
             ("%ragged-dot-none.1 custom-call", 1600, 300),    # prefill
             ("%ragged-dot-none custom-call", 3100, 100),
             ("%while.3 while", 0, 1000)],
        modules=[("jit__decode_chunk_jit(12)", 0, 1000),
                 ("jit__prefill_chunk_jit(3)", 1500, 500),
                 ("jit__decode_chunk_jit(12)", 3000, 500)])
    tr = T.Trace(devices={"/device:TPU:0": dev},
                 host=[("bench.window", 0, 4000)])
    assert reader.decode_expert_seconds(tr) == pytest.approx(400e-9)
    c = committed()
    batches = [([100, 120], [3, 1])]
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    fam = spec.family(c)
    want = sum(max(b / 819e9, f / 197e12) for b, f in (
        fam.expert_ffn_work(c, 2), fam.expert_ffn_work(c, 1),
        fam.expert_ffn_work(c, 1)))
    rec = {"kind": "rollout", "trace": tr, "config": c, "batches": batches,
           "peaks": peaks}
    assert reader.read(rec) == pytest.approx(100.0 * want / 400e-9)
    # a dense configuration or a trace with no such op reads nothing
    dense = json.loads((ROOT / "bench" / "configs" / "qwen3-1.7b.json"
                        ).read_text())
    assert reader.read(dict(rec, config=dense)) is None
    dev.ops = [o for o in dev.ops if "ragged" not in o[0]]
    assert reader.read(rec) is None


def test_held_block_is_checked():
    from repro.configs import get_config
    base = get_config("qwen3-30b-a3b")
    with pytest.raises(ValueError, match="expert_block"):
        dataclasses.replace(base, expert_block=(120, 16))
    assert dataclasses.replace(base, expert_block=[16, 16]).expert_block \
        == (16, 16)
