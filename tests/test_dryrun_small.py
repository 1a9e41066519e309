"""Dry-run machinery on a CI-sized fake mesh (subprocess so the
XLA_FLAGS device-count override never leaks into other tests). Also unit
tests for the roofline HLO parsers."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.launch.roofline import (_shape_bytes, parse_collective_bytes,
                                   parse_collectives_loop_aware)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, json, jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.config import RLConfig, TrainConfig, ShapeConfig
    from repro.configs import smoke
    from repro.launch import sharding as shd, step_fns as sf
    from repro.launch.mesh import make_debug_mesh

    mesh = make_debug_mesh(2, 2, multi_pod=True)    # (2,2,2) = 8 devices
    cfg = dataclasses.replace(smoke("{arch}"), remat=True,
                              act_sharding=shd.act_sharding_for("train",
                                                                mesh))
    shape = ShapeConfig("tiny_train", 64, 16, "train")
    rl, tc = RLConfig(group_size=4), TrainConfig()
    with mesh:
        step = sf.make_train_fn(cfg, rl, tc)
        state = sf.abstract_state(cfg)
        batch = sf.abstract_batch(cfg, shape)
        pspecs = shd.param_specs(cfg, "train", mesh)
        ss = sf.TrainState(params=pspecs,
                           opt=shd.opt_specs(pspecs, sf.optimizer_for(cfg)),
                           step=P())
        compiled = jax.jit(
            step,
            in_shardings=(shd.to_named_fit(mesh, ss, state),
                          shd.to_named_fit(mesh, shd.batch_specs(cfg, mesh),
                                           batch)),
            out_shardings=(shd.to_named_fit(mesh, ss, state), None),
        ).lower(state, batch).compile()
    hlo = compiled.as_text()
    assert "all-reduce" in hlo or "all-gather" in hlo
    from repro.launch.roofline import normalize_cost_analysis
    ca = normalize_cost_analysis(compiled.cost_analysis())
    print(json.dumps({{"ok": True, "flops": ca.get("flops", 0)}}))
""")


@pytest.mark.parametrize("arch", ["internlm2-1.8b", "mamba2-1.3b",
                                  "llama4-scout-17b-a16e"])
def test_train_step_lowers_on_multipod_debug_mesh(arch):
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", SCRIPT.format(arch=arch)],
                         capture_output=True, text=True, env=env,
                         timeout=420)
    assert out.returncode == 0, out.stderr[-3000:]
    rec = json.loads(out.stdout.strip().splitlines()[-1])
    assert rec["ok"]


class TestRooflineParsers:
    def test_shape_bytes(self):
        assert _shape_bytes("bf16[4,8]") == 64
        assert _shape_bytes("(f32[2,2], s32[4])") == 32
        assert _shape_bytes("pred[]") == 1

    def test_collective_parse(self):
        hlo = """
ENTRY %main (p0: f32[4]) -> f32[4] {
  %ag = f32[64,32]{1,0} all-gather(%x), replica_groups={}
  %ar = bf16[16]{0} all-reduce(%y), to_apply=%add
  ROOT %r = f32[4] add(%p0, %p0)
}
"""
        out = parse_collective_bytes(hlo)
        assert out["all-gather"] == 64 * 32 * 4
        assert out["all-reduce"] == 32

    def test_loop_aware_multiplies_trip_count(self):
        hlo = """
%cond (p: (s32[])) -> pred[] {
  %c = s32[] constant(5)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}
%body (p: (s32[])) -> (s32[]) {
  %ag = f32[8]{0} all-gather(%z), replica_groups={}
  ROOT %t = (s32[]) tuple(%i)
}
ENTRY %main (p0: f32[4]) -> f32[4] {
  %w = (s32[]) while(%init), condition=%cond, body=%body
  %ar = f32[4]{0} all-reduce(%p0), to_apply=%add
  ROOT %r = f32[4] add(%p0, %p0)
}
"""
        out = parse_collectives_loop_aware(hlo)
        assert out["all-gather"] == 5 * 8 * 4
        assert out["all-reduce"] == 16

    def test_roofline_peaks_keyed_by_device_kind(self):
        from repro.launch.roofline import roofline
        from repro.parallel import peak_rates
        terms = roofline(197e12, 819e9, 0.0, "TPU v5 lite")
        assert terms["compute_s"] == pytest.approx(1.0)
        assert terms["memory_s"] == pytest.approx(1.0)
        with pytest.raises(KeyError, match="no published peaks"):
            peak_rates("cpu")
