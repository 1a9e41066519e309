"""Unified execution layer: ExecutionPlan construction and placement,
sharded-step equivalence + donation on the local (1×1) plan, checkpoint
round-trip fixes (bf16 dtype preservation, pruned-version fetch)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import PolicyStore, load_pytree, save_pytree
from repro.config import ModelConfig, RLConfig, TrainConfig, ATTN, MLP
from repro.models import init_params
from repro.parallel import (ExecutionPlan, local_plan, make_sharded_train_step,
                            plan_from_flag)
from repro.training import TrainState, init_state, train_step

TINY = ModelConfig(name="tiny", family="dense", num_layers=2, d_model=48,
                   num_heads=4, num_kv_heads=2, d_ff=96, vocab_size=32,
                   block_pattern=(ATTN,), ffn_pattern=(MLP,),
                   dtype="float32", attn_impl="naive", remat=False,
                   rope_theta=1e4)
RL = RLConfig(loss_type="gepo", group_size=4, beta_kl=0.005)


def _batch(key, b=8, s=10):
    ks = jax.random.split(key, 3)
    return {
        "tokens": jax.random.randint(ks[0], (b, s), 0, 32),
        "mask": jnp.ones((b, s - 1)),
        "sampler_lp": -jnp.abs(jax.random.normal(ks[1], (b, s - 1))),
        "rewards": (jax.random.uniform(ks[2], (b,)) > 0.5).astype(
            jnp.float32),
    }


class TestExecutionPlan:
    def test_hashable_and_cached(self):
        p1, p2 = local_plan("train"), local_plan("train")
        assert p1 is p2 and hash(p1) == hash(p2)
        assert local_plan("serve") != p1
        assert plan_from_flag("1x1", "train") is p1
        assert plan_from_flag(None, "train") is p1

    def test_bad_specs_rejected(self):
        with pytest.raises(ValueError):
            ExecutionPlan(mesh=local_plan("train").mesh, mode="bogus")
        from repro.parallel import mesh_from_flag
        with pytest.raises(ValueError):
            mesh_from_flag("banana")
        with pytest.raises(RuntimeError):      # more devices than visible
            mesh_from_flag("64x64")

    def test_state_shardings_match_state_structure(self, rng):
        plan = local_plan("train")
        for optimizer in ("adamw", "adafactor"):
            state = init_state(TINY, TrainConfig(), init_params(TINY, rng),
                               optimizer=optimizer)
            sh = plan.state_shardings(TINY, optimizer)
            assert (jax.tree_util.tree_structure(state)
                    == jax.tree_util.tree_structure(sh))

    def test_device_put_and_gather_roundtrip(self, rng):
        plan = local_plan("train")
        params = init_params(TINY, rng)
        placed = plan.device_put_params(TINY, params, copy=True)
        host = plan.host_gather(placed)
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(host)):
            np.testing.assert_array_equal(np.asarray(a), b)

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
    def test_init_params_born_on_plan(self, rng, dtype):
        """The one-program init gives ``init_params``'s values, already on
        the plan's shardings (XLA fuses the scale into the sampler, so
        f32 leaves may differ in the last ulp)."""
        import dataclasses
        cfg = dataclasses.replace(TINY, dtype=dtype)
        plan = local_plan("train")
        born = plan.init_params(cfg, rng)
        eager = init_params(cfg, rng)
        for a, b, sh in zip(jax.tree_util.tree_leaves(born),
                            jax.tree_util.tree_leaves(eager),
                            jax.tree_util.tree_leaves(
                                plan.param_shardings(cfg)), strict=True):
            assert a.dtype == b.dtype and a.sharding == sh
            np.testing.assert_allclose(np.asarray(a, np.float32),
                                       np.asarray(b, np.float32),
                                       rtol=2 ** -22, atol=0)

    def test_batch_shardings_reject_unknown_keys(self):
        plan = local_plan("train")
        with pytest.raises(ValueError, match="no batch sharding rule"):
            plan.batch_shardings(TINY, {"mystery": jnp.ones((2, 2))})


class TestShardedStep:
    def test_local_plan_matches_unsharded_and_donates(self, rng):
        batch = _batch(jax.random.PRNGKey(5))
        params = init_params(TINY, rng)
        for accum in (1, 2):
            tc = TrainConfig(learning_rate=1e-3, grad_accum=accum,
                             total_steps=10)
            ref_new, ref_m = train_step(TINY, RL, tc,
                                        init_state(TINY, tc, params), batch)
            plan = local_plan("train")
            # the donated step consumes the state — give it its own copy
            # of params (device_put onto an identical sharding aliases)
            st = init_state(TINY, tc,
                            jax.tree_util.tree_map(jnp.array, params),
                            plan=plan)
            step = make_sharded_train_step(TINY, RL, tc, plan)
            new_state, m = step(st, batch)
            assert all(l.is_deleted() for l in
                       jax.tree_util.tree_leaves(st.params)), \
                "TrainState must be donated (no 2x param copies)"
            for a, b in zip(jax.tree_util.tree_leaves(ref_new.params),
                            jax.tree_util.tree_leaves(new_state.params)):
                np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                           rtol=5e-5, atol=1e-6)
            for k in ref_m:
                np.testing.assert_allclose(float(ref_m[k]), float(m[k]),
                                           rtol=1e-4, atol=1e-6)

    def test_jit_train_step_goes_through_plan(self, rng):
        from repro.training import jit_train_step
        tc = TrainConfig(learning_rate=1e-3, total_steps=10)
        f = jit_train_step(TINY, RL, tc)
        assert f.plan is local_plan("train")
        st = init_state(TINY, tc, init_params(TINY, rng), plan=f.plan)
        new_state, m = f(st, _batch(jax.random.PRNGKey(6)))
        assert np.isfinite(float(m["loss"]))


class TestCheckpointDtypes:
    def test_bf16_roundtrip_preserves_dtype_and_values(self, rng):
        tree = {"w": (jax.random.normal(rng, (4, 6)) * 3
                      ).astype(jnp.bfloat16),
                "scalar": jnp.float32(2.5),
                "nested": {"b": jnp.arange(7, dtype=jnp.bfloat16)}}
        blob = save_pytree(tree)
        back = load_pytree(blob, tree)
        for a, b in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(back)):
            assert a.dtype == b.dtype, "bf16 leaf silently changed dtype"
            np.testing.assert_array_equal(
                np.asarray(a, np.float32), np.asarray(b, np.float32))

    def test_bf16_params_roundtrip(self, rng):
        import dataclasses
        cfg = dataclasses.replace(TINY, dtype="bfloat16", name="tiny-bf16")
        params = init_params(cfg, rng)
        back = load_pytree(save_pytree(params), params)
        for a, b in zip(jax.tree_util.tree_leaves(params),
                        jax.tree_util.tree_leaves(back)):
            assert a.dtype == b.dtype
            np.testing.assert_array_equal(np.asarray(a, np.float32),
                                          np.asarray(b, np.float32))


class TestPolicyStoreFetch:
    def test_pruned_version_degrades_to_oldest_retained(self):
        store = PolicyStore(keep=2)
        for v in range(5):
            store.publish(v, bytes([v]))
        v, data = store.fetch(0)               # pruned: degrade, count
        assert (v, data) == (3, bytes([3]))
        assert store.stale_fetches == 1
        v, data = store.fetch(4)               # retained: exact
        assert (v, data) == (4, bytes([4]))
        assert store.stale_fetches == 1

    def test_never_published_version_raises_descriptive(self):
        store = PolicyStore(keep=2)
        store.publish(0, b"x")
        with pytest.raises(KeyError, match="never published"):
            store.fetch(99)

    def test_gap_version_below_prune_horizon_still_raises(self):
        """Only versions that actually went through publish() may degrade
        to the oldest retained one — a gap version (sync_interval > 1)
        is a caller bug, not staleness, wherever it falls."""
        store = PolicyStore(keep=2)
        for v in (0, 2, 4, 6):
            store.publish(v, bytes([v]))
        v, data = store.fetch(0)               # published, pruned
        assert (v, data) == (4, bytes([4])) and store.stale_fetches == 1
        with pytest.raises(KeyError, match="never published"):
            store.fetch(1)                     # below horizon, never seen
        with pytest.raises(KeyError, match="never published"):
            store.fetch(5)                     # above horizon, never seen
        assert store.stale_fetches == 1

    def test_empty_store_raises_descriptive(self):
        with pytest.raises(KeyError, match="empty"):
            PolicyStore().fetch()
