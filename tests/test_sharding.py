"""Sharding rule tests + a small-mesh lowering test in a subprocess
(XLA device count must be set before jax initializes)."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SHAPES, get_config, get_reduced_config, input_specs
from repro.distributed import sharding as sh
from repro.launch.steps import eval_param_shapes

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_mesh(shape=(16, 16), axes=("data", "model")):
    """AbstractMesh carries shape/axis info without real devices."""
    try:  # jax >= 0.5: AbstractMesh(axis_sizes, axis_names)
        return jax.sharding.AbstractMesh(shape, axes)
    except TypeError:  # jax 0.4.x: AbstractMesh(((name, size), ...))
        return jax.sharding.AbstractMesh(tuple(zip(axes, shape)))


def test_param_specs_cover_tree_and_respect_divisibility():
    mesh = _fake_mesh()
    for arch in ("qwen2_5_3b", "whisper_tiny", "mixtral_8x7b", "mamba2_1_3b"):
        cfg = get_config(arch)
        pshapes = eval_param_shapes(cfg)
        specs = sh.param_spec_tree(cfg, mesh, "train", pshapes)
        flat_p = jax.tree_util.tree_leaves_with_path(pshapes)
        flat_s = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, P))
        assert len(flat_p) == len(flat_s)
        for (path, leaf), spec in zip(flat_p, flat_s):
            assert len(spec) <= len(leaf.shape)
            # every sharded dim divides the axis product
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = (ax,) if isinstance(ax, str) else ax
                total = 1
                for a in axes:
                    total *= mesh.shape[a]
                assert leaf.shape[dim] % total == 0, (arch, path, spec, leaf.shape)


def test_gqa_kv_not_split_within_heads():
    """qwen kv=2 on a 16-wide model axis: wk/wv must not shard their
    output dim (would split inside a head -> per-layer K/V gathers)."""
    mesh = _fake_mesh()
    cfg = get_config("qwen2_5_3b")
    pshapes = eval_param_shapes(cfg)
    specs = sh.param_spec_tree(cfg, mesh, "serve", pshapes)
    wk_spec = specs["blocks"]["attn"]["wk"]
    assert wk_spec[-1] is None
    # q heads (16) divide the axis -> wq IS sharded
    assert specs["blocks"]["attn"]["wq"][-1] == "model"


def test_moe_expert_sharding_rules():
    mesh = _fake_mesh()
    arctic = get_config("arctic_480b")  # 128 experts % 16 == 0
    sp = sh.param_spec_tree(arctic, mesh, "train", eval_param_shapes(arctic))
    assert sp["blocks"]["moe"]["wg"][-3] == "model"  # expert dim
    mix = get_config("mixtral_8x7b")  # 8 experts, not divisible
    sp2 = sh.param_spec_tree(mix, mesh, "train", eval_param_shapes(mix))
    assert sp2["blocks"]["moe"]["wg"][-3] is None
    assert sp2["blocks"]["moe"]["wg"][-1] == "model"  # FFN dim instead


def test_cache_specs_match_cache_tree():
    mesh = _fake_mesh()
    for arch in ("qwen2_5_3b", "mamba2_1_3b", "zamba2_7b", "whisper_tiny"):
        cfg = get_config(arch)
        shape = SHAPES["decode_32k"]
        specs = input_specs(cfg, shape)
        ctree = sh.cache_spec_tree(cfg, mesh, specs["cache"])
        assert set(ctree) == set(specs["cache"])


@pytest.mark.slow
def test_reduced_arch_lowering_on_small_mesh():
    """Lower+compile a reduced arch train step on an 8-device (2,4) mesh
    in a subprocess (device count is locked at first jax init)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_reduced_config
        from repro.distributed import sharding as sh
        from repro.launch.steps import make_train_step, eval_param_shapes, eval_opt_shapes
        cfg = get_reduced_config("mixtral_8x7b")
        mesh = jax.make_mesh((2, 4), ("data", "model"))
        pshapes = eval_param_shapes(cfg)
        praw = sh.param_spec_tree(cfg, mesh, "train", pshapes)
        pspecs = sh.named(mesh, praw)
        oshapes = eval_opt_shapes(cfg, pshapes)
        ospecs = sh.named(mesh, sh.opt_state_specs(praw))
        step = make_train_step(cfg)
        B, S = 4, 128
        batch = {
            "tokens": jax.ShapeDtypeStruct((B, S), jnp.int32),
            "labels": jax.ShapeDtypeStruct((B, S), jnp.int32),
        }
        bspec = sh.named(mesh, {"tokens": P("data", None), "labels": P("data", None)})
        with mesh:
            comp = jax.jit(step, in_shardings=(pspecs, ospecs, bspec),
                           out_shardings=(pspecs, ospecs, None),
                           donate_argnums=(0, 1)).lower(pshapes, oshapes, batch).compile()
        print("COMPILED_OK", comp.cost_analysis().get("flops", 0) > 0 if not isinstance(comp.cost_analysis(), list) else True)
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    env["JAX_PLATFORMS"] = "cpu"  # virtual host devices, never the chip
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=600)
    assert "COMPILED_OK" in out.stdout, out.stderr[-2000:]
