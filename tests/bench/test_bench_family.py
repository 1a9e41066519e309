"""The dense Qwen3 family (``bench/models/qwen3.py``) draws, runs and
counts what the benchmark did before model families were files: the
golden numbers below were read from the code they replaced. A family is
found by its configuration's ``model_type``."""
import hashlib
import json
import shutil

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny import ROOT, tiny_config

from bench.lib import program, reference, spec, work
from bench.lib import weights as W

SEED = 2 ** 31 + 11

# sha256 (first 16 hex digits) of each leaf's float32 bytes, tiny config
TINY_LEAVES = {
    "attn_norm": "02722f124d0f1736", "embed": "5a0b16e5e3a90f15",
    "final_norm": "2f20cd03c9cd392a", "lm_head": "8555c54d8f0f6b8e",
    "mlp_norm": "02722f124d0f1736", "w_down": "3313b7f21822f15b",
    "w_gate": "4ab508206ef5f118", "w_up": "dd41bf0fb63da3e3",
    "wk": "738c20b797019a24", "wo": "ffe3c62476678431",
    "wq": "f1807ce097ce4d70", "wv": "6189f29e19c61d13"}

QWEN3_8B_SHAPES = {
    "attn_norm": (36, 4096), "embed": (152064, 4096), "final_norm": (4096,),
    "lm_head": (4096, 152064), "mlp_norm": (36, 4096),
    "w_down": (36, 12288, 4096), "w_gate": (36, 4096, 12288),
    "w_up": (36, 4096, 12288), "wk": (36, 4096, 1024),
    "wo": (36, 4096, 4096), "wq": (36, 4096, 4096), "wv": (36, 4096, 1024)}

# the reference's logits over 2 x 24 tokens: sum, sum of |x|, x[1, 5, 7]
TINY_LOGITS = {True: (-0.7414773363419727, 3144.8079271806705,
                      -0.2460479736328125),
               False: (62.08332533161138, 3130.5139299634066,
                       0.050040654838085175)}

WORK = {
    "qwen3-1.7b": {"matmul_params": 1720451072, "weight_bytes": 3441135616,
                   "kv_bytes_per_token": 114688,
                   "train_flops": 14452209156096.0,
                   "decode_least": 0.021102116884004884},
    "qwen3-8b": {"matmul_params": 7568097280, "weight_bytes": 15136792576,
                 "kv_bytes_per_token": 147456,
                 "train_flops": 62856247246848.0,
                 "decode_least": 0.09253102058119658},
}


def published(name):
    """A configuration file with any cut undone (``published``)."""
    c = json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())
    return dict(c, **c.get("published", {}))


@pytest.mark.parametrize("tied", [True, False])
def test_tiny_weights_are_bit_identical(tied):
    c = tiny_config(tied)
    wts = W.make(c, SEED, program.model_config(c).padded_vocab)
    got = {n: hashlib.sha256(np.asarray(a).tobytes()).hexdigest()[:16]
           for n, a in wts.items()}
    want = {n: h for n, h in TINY_LEAVES.items()
            if tied is False or n != "lm_head"}
    assert got == want


def test_qwen3_8b_leaf_shapes():
    c = published("qwen3-8b")
    got = jax.eval_shape(lambda: W.make(c, 0, 152064))
    assert {n: a.shape for n, a in got.items()} == QWEN3_8B_SHAPES
    assert {str(a.dtype) for a in got.values()} == {"bfloat16"}


@pytest.mark.parametrize("tied", [True, False])
def test_reference_forward_unchanged(tied):
    c = tiny_config(tied)
    wts = W.make(c, SEED, program.model_config(c).padded_vocab)
    tokens = np.random.default_rng(0).integers(3, 512, (2, 24)).astype(np.int32)
    x, _ = reference.hidden(c, wts, jnp.asarray(tokens), "f32")
    h = reference.rmsnorm(x, wts["final_norm"], c["rms_norm_eps"])
    lg = np.asarray(jnp.matmul(h, reference.head_matrix(wts, c["vocab_size"]),
                               precision=reference.HI)).astype(np.float64)
    got = (lg.sum(), np.abs(lg).sum(), lg[1, 5, 7])
    np.testing.assert_allclose(got, TINY_LOGITS[tied], rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("name", sorted(WORK))
def test_work_counts_unchanged(name):
    c = published(name)
    want = WORK[name]
    assert work.matmul_params(c) == want["matmul_params"]
    assert work.weight_bytes(c, 1) == want["weight_bytes"]
    assert work.kv_bytes_per_token(c) == want["kv_bytes_per_token"]
    assert work.train_flops(c, [1, 37, 300, 1024]) == want["train_flops"]
    assert work.decode_least_seconds(c, [10, 200], [5, 3], 197e12,
                                     819e9) == want["decode_least"]


def test_family_found_by_model_type(tmp_path):
    b = tmp_path / "bench"
    (b / "models").mkdir(parents=True)
    shutil.copy(ROOT / "bench" / "models" / "qwen3.py",
                b / "models" / "toy_moe.py")
    with open(b / "models" / "toy_moe.py", "a") as f:
        f.write("\nTOY = True\n")
    fam = spec.family({"model_type": "toy_moe"}, bench_dir=b)
    assert fam.TOY and fam.LAYER_LEAVES
    assert spec.family(tiny_config()) is spec.family({"model_type": "qwen3"})
    with pytest.raises(FileNotFoundError, match="models/nothing.py"):
        spec.family({"model_type": "nothing"}, bench_dir=b)
