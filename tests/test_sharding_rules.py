"""Unit tests for mesh construction and sharding rules (no 512-device
requirement -- specs are validated structurally)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.launch import sharding as shd
from repro.launch.mesh import batch_axes, make_host_mesh
from repro.models.transformer import init_params


class FakeMesh:
    """Duck-typed mesh exposing shape/axis_names for spec computation."""

    def __init__(self, shape_map):
        self.shape = shape_map
        self.axis_names = tuple(shape_map)


MESH = FakeMesh({"data": 16, "model": 16})


class TestSpecRules:
    def test_embed_vocab_sharded(self):
        leaf = jax.ShapeDtypeStruct((262144, 1152), jnp.bfloat16)
        spec = shd._spec_for_param("['embed']", leaf, ARCHS["gemma3-1b"], MESH)
        assert spec == P("model", None)

    def test_attn_projections(self):
        cfg = ARCHS["qwen1.5-0.5b"]
        wq = jax.ShapeDtypeStruct((24, 1024, 1024), jnp.bfloat16)
        spec = shd._spec_for_param("['groups'][0]['attn']['wq']", wq, cfg, MESH)
        assert spec == P(None, None, "model")
        wo = jax.ShapeDtypeStruct((24, 1024, 1024), jnp.bfloat16)
        spec = shd._spec_for_param("['groups'][0]['attn']['wo']", wo, cfg, MESH)
        assert spec == P(None, "model", None)

    def test_moe_expert_parallel_when_divisible(self):
        cfg = ARCHS["llama4-maverick-400b-a17b"]  # 128 experts % 16 == 0
        w = jax.ShapeDtypeStruct((24, 128, 5120, 8192), jnp.bfloat16)
        spec = shd._spec_for_param("['groups'][1]['moe']['w_in']", w, cfg, MESH)
        assert spec == P(None, "data", None, "model")

    def test_moe_tensor_parallel_when_not_divisible(self):
        cfg = ARCHS["grok-1-314b"]  # 8 experts % 16 != 0
        w = jax.ShapeDtypeStruct((64, 8, 6144, 32768), jnp.bfloat16)
        spec = shd._spec_for_param("['groups'][0]['moe']['w_in']", w, cfg, MESH)
        assert spec == P(None, None, "data", "model")

    def test_sanitize_drops_nondivisible(self):
        spec = shd._sanitize(P("model", None), (32001, 1600), MESH)
        assert spec == P(None, None)
        spec = shd._sanitize(P("model", None), (32000, 1600), MESH)
        assert spec == P("model", None)

    def test_sanitize_tuple_axes(self):
        mesh = FakeMesh({"pod": 2, "data": 16, "model": 16})
        spec = shd._sanitize(P(("pod", "data"), None), (256, 4), mesh)
        assert spec == P(("pod", "data"), None)
        spec = shd._sanitize(P(("pod", "data"), None), (100, 4), mesh)
        assert spec == P(None, None)


class TestBatchAxes:
    def test_single_pod(self):
        assert batch_axes(FakeMesh({"data": 16, "model": 16})) == ("data",)

    def test_multi_pod(self):
        assert batch_axes(FakeMesh({"pod": 2, "data": 16, "model": 16})) == (
            "pod",
            "data",
        )


class TestRealShardedExecution:
    """End-to-end sharded forward on the real (single-device) mesh."""

    def test_param_shardings_cover_tree(self):
        cfg = ARCHS["gemma3-1b"].reduced()
        params = jax.eval_shape(lambda: init_params(cfg, jax.random.PRNGKey(0)))
        mesh = make_host_mesh(1, 1)
        shards = shd.param_shardings(cfg, mesh, params)
        assert jax.tree.structure(shards) == jax.tree.structure(params)

    @pytest.mark.parametrize("name", ["qwen1.5-0.5b", "grok-1-314b", "rwkv6-7b"])
    def test_forward_under_mesh(self, name):
        from repro.models.frontend import make_train_batch
        from repro.models.transformer import forward_loss

        cfg = ARCHS[name].reduced()
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        batch = make_train_batch(cfg, 2, 32)
        mesh = make_host_mesh(1, 1)
        with jax.set_mesh(mesh):
            loss, _ = jax.jit(
                lambda p, b: forward_loss(cfg, p, b, remat=False)
            )(params, batch)
        assert np.isfinite(float(loss))

    @pytest.mark.parametrize("name", ["qwen1.5-0.5b", "grok-1-314b"])
    def test_constraints_reach_lowered_program(self, name):
        """Under a set mesh ``constrain`` must emit sharding constraints;
        a ``constrain`` that silently returns its input fails here."""
        from repro.models.frontend import make_train_batch
        from repro.models.transformer import forward_loss

        cfg = ARCHS[name].reduced()
        params = init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)
        batch = make_train_batch(cfg, 2, 32)
        fn = jax.jit(lambda p, b: forward_loss(cfg, p, b, remat=False))
        assert "sharding_constraint" not in fn.lower(params, batch).as_text()
        with jax.set_mesh(make_host_mesh(1, 1)):
            text = fn.lower(params, batch).as_text()
        assert "sharding_constraint" in text
