"""Table 14 / App. F: localized reward computation.

Two implementations of group-advantage normalization are lowered on an
8-device fake mesh (subprocess, so the device-count override stays
contained):

  global   — rewards all-gathered, batch statistics computed globally
             (the "before" column of Table 14)
  localized — per-group statistics with groups aligned to shards
             (the paper's optimization: no collective at all)

The measured quantity is collective bytes in the compiled HLO.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json, jax, jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.roofline import parse_collectives_loop_aware

    from repro.parallel import make_mesh
    mesh = make_mesh((8,), ("data",))
    B, G = 256, 8
    sh = NamedSharding(mesh, P("data"))

    def localized(rewards):
        r = rewards.reshape(B // G, G)
        a = (r - r.mean(-1, keepdims=True)) / (r.std(-1, keepdims=True)
                                               + 1e-6)
        return a.reshape(B)

    def global_stats(rewards):
        # pre-App.-F implementations normalize with *global* batch stats
        mu = rewards.mean()
        sd = rewards.std()
        r = rewards.reshape(B // G, G)
        a = (r - r.mean(-1, keepdims=True)) / (sd + 1e-6) + 0 * mu
        return a.reshape(B)

    out = {}
    with mesh:
        for name, fn in [("localized", localized),
                         ("global", global_stats)]:
            c = jax.jit(fn, in_shardings=sh, out_shardings=sh).lower(
                jax.ShapeDtypeStruct((B,), jnp.float32)).compile()
            coll = parse_collectives_loop_aware(c.as_text())
            out[name] = int(sum(coll.values()))
    print(json.dumps(out))
""")


def run() -> list:
    # a virtual-device lowering on the CPU, never a second process onto
    # an accelerator the parent may hold
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", SCRIPT],
                         capture_output=True, text=True, env=env,
                         timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    rec = json.loads(res.stdout.strip().splitlines()[-1])
    rows = ["table14_localized,variant,collective_bytes_per_step"]
    rows.append(f"table14_localized,global_gather,{rec['global']}")
    rows.append(f"table14_localized,localized(ours),{rec['localized']}")
    assert rec["localized"] <= rec["global"]
    assert rec["localized"] == 0, \
        "localized reward computation must need NO collectives"
    return rows
