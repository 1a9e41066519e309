"""Numerical equivalence of the shard_map expert-parallel MoE (§Perf
optimization) against the GSPMD baseline dispatch — run on an 8-device
debug mesh in a subprocess (device-count override must not leak)."""
import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json
    import jax, jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.config import ModelConfig, ATTN, MOE
    from repro.models.moe import moe_ffn
    from repro.models.moe_ep import moe_ffn_ep
    from repro.models.params import init_params
    from repro.runtime_context import mesh_context

    from repro.parallel import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = ModelConfig(name="moe-eq", family="moe", num_layers=1,
                      d_model=64, num_heads=4, num_kv_heads=2, d_ff=128,
                      vocab_size=256, block_pattern=(ATTN,),
                      ffn_pattern=(MOE,), num_experts=4,
                      experts_per_token={k}, dtype="float32",
                      attn_impl="naive", remat=False)
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)["blocks"]["layer_0"]["moe"]
    params = jax.tree_util.tree_map(lambda a: a[0], params)  # unstack
    x = 0.5 * jax.random.normal(jax.random.PRNGKey(1), (4, 16, 64))

    y_ref, aux_ref = moe_ffn(cfg, params, x)     # single-device baseline

    cfg_ep = dataclasses.replace(cfg, moe_ep="serve",
                                 ep_dp_axes=("data",))
    with mesh_context(mesh):
        def f(params, x):
            return moe_ffn_ep(cfg_ep, params, x)
        y_ep, aux_ep = jax.jit(f)(params, x)

    err = float(jnp.abs(y_ref - y_ep).max())
    lb_err = abs(float(aux_ref["moe_load_balance"])
                 - float(aux_ep["moe_load_balance"]))
    print(json.dumps({{"err": err, "lb_err": lb_err}}))
""")


def _run(k: int):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT.format(k=k)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_ep_matches_gspmd_top1():
    rec = _run(1)
    assert rec["err"] < 1e-4, rec
    assert rec["lb_err"] < 0.1, rec   # mean-of-shard-means


def test_ep_matches_gspmd_top2():
    rec = _run(2)
    assert rec["err"] < 1e-4, rec
