"""Plain float32 reference of the configured model, its GEPO loss and
its Adafactor step. It imports nothing of the program and takes nothing
the program made: weights come from ``bench.lib.weights`` (the seed),
batches from ``bench.lib.traffic``.

The layers are those of the configuration's model family
(``bench/models/<model_type>.py``, its ``layer``), as the configuration
file states them, with the departures that file lists; the embedding,
the final norm, the LM head, the loss, the optimizer and the readings
of served tokens are here. Every matmul runs at ``Precision.HIGHEST``;
``mm="fp8"`` is the control: each linear layer's weights (per output
column) and inputs (per row) rounded to float8_e4m3fn with an absmax
scale, the precision below the configured bfloat16.

Everything runs in blocks so that it fits next to nothing else on the
cell's chips: the learner reference keeps the weights in bfloat16 (their
stored type; each layer is widened to float32 as it is used),
accumulates an f32 gradient layer by layer through ``jax.vjp`` of one
layer at a time, and reads the LM head in chunks of positions. On more
than one chip the weights, the gradient and the optimizer's statistics
are split over a mesh of them (``bench.lib.placement``); on one, the
programs are the one-device programs.
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib import placement, spec
from bench.lib.weights import config_items

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0
HEAD_CHUNK = 256


def _q8(x: jax.Array, axis: int) -> jax.Array:
    """Round to float8_e4m3fn with an absmax scale along ``axis``. The
    rounding is in the values only: gradients pass straight through in
    float32 (as fp8 training recipes scale them), so a backward pass sees
    the rounded operands, not cotangents flushed to zero."""
    s = jax.lax.stop_gradient(
        jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX)
    s = jnp.where(s > 0, s, 1.0)
    # x / s can land a rounding step past the largest float8 value, which
    # the cast does not saturate on every backend: clip before it
    q = jnp.clip(x / s, -F8_MAX, F8_MAX).astype(F8).astype(jnp.float32) * s
    return x + jax.lax.stop_gradient(q - x)


def matmul(x: jax.Array, w: jax.Array, mm: str) -> jax.Array:
    """x (..., k) @ w (k, n) in float32; "fp8" rounds both operands."""
    w = w.astype(jnp.float32)
    if mm == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * g.astype(jnp.float32)


def rope(x, pos, theta):
    """Rotate the two halves of each head (the HF ``rotate_half`` form)."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[..., None].astype(jnp.float32) * freq           # (R, W, half)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def head_matrix(wts: Dict[str, jax.Array], vocab: int) -> jax.Array:
    """(d, V) LM head over the published vocabulary."""
    if "lm_head" in wts:
        return wts["lm_head"][:, :vocab]
    return wts["embed"][:vocab].T


def hidden(c, wts, tokens, mm, keep: bool = False):
    """Embed and run every layer. Returns the last hidden state and, with
    ``keep``, every layer's input (L, R, W, d)."""
    fam = spec.family(c)
    x = wts["embed"][tokens].astype(jnp.float32)

    def body(x, lw):
        return fam.layer(c, lw, x, mm), (x if keep else None)

    x, xs = jax.lax.scan(body, x, {n: wts[n] for n in fam.LAYER_LEAVES})
    return x, xs


def _chunks(w: int) -> int:
    return max(1, w // HEAD_CHUNK) if w % HEAD_CHUNK == 0 else 1


def head_logps(c, fn, head, x, targets, mm):
    """log p(targets) (R, W) from the last hidden state, by chunks of
    positions (checkpointed: a backward pass recomputes each chunk)."""
    r, w, d = x.shape
    n = _chunks(w)
    h = rmsnorm(x, fn, c["rms_norm_eps"])

    @jax.checkpoint
    def one(args):
        hc, tc = args
        lg = matmul(hc, head, mm)                          # (R, C, V)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.take_along_axis(lg, tc[..., None], -1)[..., 0] - lse

    hs = h.reshape(r, n, w // n, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(r, n, w // n).transpose(1, 0, 2)
    lp = jax.lax.map(one, (hs, ts))
    return lp.transpose(1, 0, 2).reshape(r, w)


# --------------------------------------------------------------------------
# GEPO loss (the configured objective, written plainly)


def group_constants(rl: Dict[str, Any], batch: Dict[str, np.ndarray],
                    micro_rows: int) -> Dict[str, np.ndarray]:
    """Per-row data constants of the loss: advantage, GEPO's log
    group-expectation denominator, and the weights that turn per-row sums
    into the program's objective. The program averages the loss over
    micro-batches of ``micro_rows`` whole groups, each normalizing its
    KL term by its own token count, so the weights follow that."""
    g = rl["group_size"]
    r = np.asarray(batch["rewards"], np.float64).reshape(-1, g)
    a = r - r.mean(-1, keepdims=True)
    if rl["adv_normalize"]:
        a = a / (r.std(-1, keepdims=True) + 1e-6)
    mask = np.asarray(batch["mask"], np.float64)
    slp = np.asarray(batch["sampler_lp"], np.float64)
    n_tok = mask.sum(-1)
    q = (slp * mask).sum(-1) / np.maximum(n_tok if rl["seq_len_normalize"]
                                          else 1.0, 1.0)
    qg = q.reshape(-1, g)

    def lse(z):
        m = z.max(-1, keepdims=True)
        return (m + np.log(np.exp(z - m).sum(-1, keepdims=True)))[..., 0]

    log_den = np.repeat(lse(2 * qg) - lse(qg), g)
    b = mask.shape[0]
    n_mb = b // micro_rows
    mb_tokens = np.repeat(mask.reshape(n_mb, -1).sum(-1), micro_rows)
    return {"adv": a.reshape(-1).astype(np.float32),
            "log_den": log_den.astype(np.float32),
            "pol_w": np.full(b, 1.0 / b, np.float32),
            "kl_w": (rl["beta_kl"] / (n_mb * np.maximum(mb_tokens, 1.0))
                     ).astype(np.float32)}


def row_loss(rl, lp, slp, mask, adv, log_den, pol_w, kl_w):
    """Sum over rows of each row's share of the objective; lp (R, W)."""
    n = mask.sum(-1)
    p = (lp * mask).sum(-1) / (jnp.maximum(n, 1.0)
                               if rl["seq_len_normalize"] else 1.0)
    w = jnp.exp(p - log_den)
    eps = rl["clip_eps"]
    pol = -jnp.minimum(w * adv, jnp.clip(w, 1 - eps, 1 + eps) * adv)
    d = slp - lp
    k3 = jnp.exp(jnp.clip(d, -20.0, 20.0)) - d - 1.0
    return jnp.sum(pol * pol_w + kl_w * (k3 * mask).sum(-1))


# --------------------------------------------------------------------------
# learner reference: loss and gradient of one block of rows


def _head_chunks(h, targets, n):
    r, w, d = h.shape
    hs = h.reshape(r, n, w // n, d).swapaxes(0, 1)
    ts = targets.reshape(r, n, w // n).swapaxes(0, 1)
    return hs, ts


@functools.partial(jax.jit, static_argnames=("citems", "rl_items", "mm",
                                             "mesh"), donate_argnums=(1,))
def _block_grad(wts, acc, tokens, slp, mask, adv, log_den, pol_w, kl_w,
                citems, rl_items, mm, mesh):
    """Add one block of rows' share of the loss gradient to ``acc``.

    The LM head's backward is written out by chunks of positions, so
    that neither the (R, W, V) logits nor a float32 copy of the head is
    ever whole; every layer's backward is ``jax.vjp`` of that one layer,
    recomputed from its stored input, added into its slice of ``acc``."""
    c, rl = dict(citems), dict(rl_items)
    v = c["vocab_size"]
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    head = head_matrix(wts, v)                             # (d, V) stored
    x_last, xs = hidden(c, wts, inp, mm, keep=True)
    h, norm_vjp = jax.vjp(
        lambda x, g: rmsnorm(x, g, c["rms_norm_eps"]), x_last,
        wts["final_norm"].astype(jnp.float32))
    n = _chunks(h.shape[1])
    hs, ts = _head_chunks(h, tgt, n)

    def fwd(args):
        hc, tc = args
        lg = matmul(hc, head, mm)
        return jnp.take_along_axis(lg, tc[..., None], -1)[..., 0] \
            - jax.nn.logsumexp(lg, -1)

    lp = jax.lax.map(fwd, (hs, ts)).swapaxes(0, 1).reshape(tgt.shape)
    loss, dlp = jax.value_and_grad(
        lambda lp: row_loss(rl, lp, slp, mask, adv, log_den, pol_w, kl_w))(lp)
    dls = dlp.reshape(dlp.shape[0], n, -1).swapaxes(0, 1)
    tied = "lm_head" not in acc

    def bwd(dhead, args):
        hc, tc, dc = args
        lg = matmul(hc, head, mm)
        p = jax.nn.softmax(lg, -1)
        hit = jnp.arange(v)[None, None, :] == tc[..., None]
        dlg = dc[..., None] * (hit.astype(jnp.float32) - p)
        dh = matmul(dlg, head.T, mm)
        hf, gf = hc.reshape(-1, hc.shape[-1]), dlg.reshape(-1, v)
        if tied:                                           # (V_pad, d)
            dhead = dhead.at[:v].add(matmul(gf.T, hf, mm))
        else:                                              # (d, V_pad)
            dhead = dhead.at[:, :v].add(matmul(hf.T, gf, mm))
        return dhead, dh

    acc = dict(acc)
    key = "embed" if tied else "lm_head"
    acc[key], dh = jax.lax.scan(bwd, acc[key], (hs, ts, dls))
    dh = dh.swapaxes(0, 1).reshape(h.shape)
    dx, dfn = norm_vjp(dh)
    acc["final_norm"] = acc["final_norm"] + dfn

    n_layers = c["num_hidden_layers"]
    fam = spec.family(c)
    stacked = fam.LAYER_LEAVES

    def back(i, carry):
        st, dx = carry
        li = n_layers - 1 - i
        lw = {k: jax.lax.dynamic_index_in_dim(wts[k], li, keepdims=False
                                              ).astype(jnp.float32)
              for k in stacked}
        x_in = jax.lax.dynamic_index_in_dim(xs, li, keepdims=False)
        _, vjp = jax.vjp(lambda lw, x: fam.layer(c, lw, x, mm), lw, x_in)
        dlw, dx = vjp(dx)
        st = {k: jax.lax.dynamic_update_index_in_dim(
            st[k], jax.lax.dynamic_index_in_dim(st[k], li, keepdims=False)
            + dlw[k], li, 0) for k in stacked}
        return st, dx

    st, dx = jax.lax.fori_loop(0, n_layers, back,
                               ({k: acc[k] for k in stacked}, dx))
    acc.update(st)
    acc["embed"] = acc["embed"].at[inp].add(dx)
    return placement.constrain(acc, mesh), loss


@functools.partial(jax.jit, static_argnames=("citems", "mm"))
def _block_logps(wts, tokens, citems, mm):
    c = dict(citems)
    x_last, _ = hidden(c, wts, tokens[:, :-1], mm)
    return head_logps(c, wts["final_norm"], head_matrix(
        wts, c["vocab_size"]), x_last, tokens[:, 1:], mm)


def _width_bucket(n: int, lo: int = 256) -> int:
    w = lo
    while w < n:
        w *= 2
    return w


def _blocks(lengths: Sequence[int], rows: int, width: int
            ) -> List[Tuple[np.ndarray, int]]:
    """Rows grouped by length into blocks of ``rows``, each with the
    power-of-two width (from 256) that holds its longest row."""
    order = np.argsort(np.asarray(lengths), kind="stable")
    out = []
    for i in range(0, len(order), rows):
        idx = order[i:i + rows]
        w = min(_width_bucket(int(max(lengths[j] for j in idx))), width)
        out.append((idx, w))
    return out


@functools.partial(jax.jit, static_argnames=("lr", "clip", "decay", "mesh"),
                   donate_argnums=(0, 2, 3))
def _adafactor(wts, grads, vr, vc, lr, clip, mesh, decay=0.999):
    """The configured optimizer step: global-norm clipping, then the
    factored second moment, update clipping to RMS 1 over each leaf, and
    the new weights rounded to their stored bfloat16."""
    eps = 1e-30
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in grads.values()))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
    new_w, new_r, new_c, norms = {}, {}, {}, {}
    for n, g in grads.items():
        g = g * scale
        norms[n] = jnp.sqrt(jnp.sum(g * g))
        g2 = g * g + eps
        if g.ndim >= 2:
            r = decay * vr[n] + (1 - decay) * g2.mean(-1)
            cc = decay * vc[n] + (1 - decay) * g2.mean(-2)
            rn = r / jnp.maximum(r.mean(-1, keepdims=True), eps)
            denom = jnp.sqrt(rn[..., None] * cc[..., None, :])
        else:
            r = decay * vr[n] + (1 - decay) * g2
            cc = vc[n]
            denom = jnp.sqrt(r)
        delta = g / jnp.maximum(denom, eps)
        delta = delta / jnp.maximum(1.0, jnp.sqrt(jnp.mean(delta * delta)
                                                  + eps))
        new_w[n] = (wts[n].astype(jnp.float32) - lr * delta).astype(
            wts[n].dtype)
        new_r[n], new_c[n] = r, cc
    new_w, new_r, new_c = placement.constrain((new_w, new_r, new_c), mesh)
    return new_w, new_r, new_c, norms, gnorm


def _zeros_like_f32(wts, mesh=None):
    return {n: placement.zeros(n, w.shape, mesh) for n, w in wts.items()}


def adafactor_state(wts, mesh=None):
    vr = {n: placement.zeros(n, w.shape[:-1] if w.ndim >= 2 else w.shape,
                             mesh) for n, w in wts.items()}
    vc = {n: placement.zeros(n, w.shape[:-2] + w.shape[-1:] if w.ndim >= 2
                             else (1,), mesh) for n, w in wts.items()}
    return vr, vc


def learn_steps(c: Dict[str, Any], rl: Dict[str, Any], wts: Dict[str, Any],
                batches: Sequence[Dict[str, np.ndarray]], *, lr: float,
                clip: float, micro_rows: int, mm: str = "f32",
                block_rows: int = 4, mesh=None,
                log: Callable[[str], None] = lambda s: None
                ) -> Dict[str, Any]:
    """Follow the learner through ``batches`` from ``wts`` (consumed;
    split over ``mesh`` where one is given, as ``weights.make`` draws
    them).

    Returns the loss of each step (before its update), every leaf's
    gradient norm as the optimizer got it at step 1 (after clipping),
    and the weights after the last step."""
    citems = config_items(c)
    rl_items = tuple(sorted(rl.items()))
    put = functools.partial(placement.host, m=mesh)
    vr, vc = adafactor_state(wts, mesh)
    losses, grad_norms, gnorms = [], None, []
    for step, batch in enumerate(batches):
        k = group_constants(rl, batch, micro_rows)
        tokens = np.asarray(batch["tokens"])
        width = tokens.shape[1] - 1
        lengths = [int(x) for x in np.asarray(batch["lengths"])]
        acc = _zeros_like_f32(wts, mesh)
        loss = 0.0
        for idx, w in _blocks(lengths, block_rows, width):
            acc, part = _block_grad(
                wts, acc, put(tokens[idx, :w + 1]),
                put(batch["sampler_lp"][idx, :w]),
                put(batch["mask"][idx, :w]),
                put(k["adv"][idx]), put(k["log_den"][idx]),
                put(k["pol_w"][idx]), put(k["kl_w"][idx]),
                citems=citems, rl_items=rl_items, mm=mm, mesh=mesh)
            loss += float(part)
        losses.append(loss)
        wts, vr, vc, norms, gnorm = _adafactor(wts, acc, vr, vc, lr=lr,
                                               clip=clip, mesh=mesh)
        del acc
        gnorms.append(float(gnorm))
        if grad_norms is None:
            grad_norms = {n: float(v) for n, v in norms.items()}
        log(f"reference[{mm}] step {step + 1}: loss {loss!r} "
            f"grad_norm {gnorms[-1]!r}")
    return {"losses": losses, "grad_norms": grad_norms, "weights": wts,
            "global_grad_norms": gnorms}


# --------------------------------------------------------------------------
# served tokens: log-probs and the gap of a token below the best one


@functools.partial(jax.jit, static_argnames=("citems", "mm"))
def _served_logits_readings(wts, tokens, keys, query, valid, citems, mm):
    """For rows ``tokens`` (R, W+1) and, at each position, the token drawn
    there (``tokens[:, 1:]``), its draw's PRNG key (R, W, 2) and a query
    token: the log-prob of the drawn token, the index of the best
    Gumbel-perturbed logit, and how far the query token's perturbed logit
    lies below that best one. Temperature 1, no filtering: the draw is
    argmax(logit + Gumbel(key)) over the padded vocabulary, whose padding
    ids the engine masks."""
    c = dict(citems)
    v = c["vocab_size"]
    x_last, _ = hidden(c, wts, tokens[:, :-1], mm)
    r, w, _ = x_last.shape
    n = _chunks(w)
    h = rmsnorm(x_last, wts["final_norm"], c["rms_norm_eps"])
    head = head_matrix(wts, v)
    v_pad = wts["embed"].shape[0]

    def one(args):
        hc, tc, kc, qc = args
        lg = matmul(hc, head, mm)                          # (R, C, V)
        lse = jax.nn.logsumexp(lg, -1)
        lp = jnp.take_along_axis(lg, tc[..., None], -1)[..., 0] - lse
        gum = jax.vmap(jax.vmap(lambda k: jax.random.gumbel(
            jax.random.wrap_key_data(k), (v_pad,), jnp.float32)))(kc)
        z = lg + gum[..., :v]
        best = jnp.argmax(z, -1)
        gap = jnp.max(z, -1) - jnp.take_along_axis(z, qc[..., None], -1)[..., 0]
        return lp, best, gap

    split = lambda a: a.reshape((r, n, w // n) + a.shape[2:]).swapaxes(0, 1)
    lp, best, gap = jax.lax.map(one, (split(h), split(tokens[:, 1:]),
                                      split(keys), split(query)))
    join = lambda a: a.swapaxes(0, 1).reshape(r, w)
    lp, best, gap = join(lp), join(best), join(gap)
    return (jnp.where(valid, lp, 0.0), jnp.where(valid, best, 0),
            jnp.where(valid, gap, 0.0))


def served_readings(c: Dict[str, Any], wts: Dict[str, Any],
                    tokens: np.ndarray, keys: np.ndarray, query: np.ndarray,
                    valid: np.ndarray, mm: str = "f32", block_rows: int = 4,
                    mesh=None):
    """``_served_logits_readings`` over blocks of rows, as numpy; the
    rows whole on every chip of ``mesh`` where one is given."""
    citems = config_items(c)
    put = functools.partial(placement.host, m=mesh)
    outs = []
    for i in range(0, tokens.shape[0], block_rows):
        sl = slice(i, i + block_rows)
        outs.append([np.asarray(a) for a in _served_logits_readings(
            wts, put(tokens[sl]), put(keys[sl]), put(query[sl]),
            put(valid[sl]), citems=citems, mm=mm)])
    return tuple(np.concatenate([o[j] for o in outs]) for j in range(3))
