"""Launch-path coverage: the dry-run machinery (abstract params/opt-state,
cache specs, lowering builders, roofline parsing) exercised on a small
8-device mesh in subprocesses (mirrors launch/dryrun.py on the production
512-device mesh, which runs outside pytest)."""
import os
import subprocess
import sys
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 560) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count={devices}"
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
    """)
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env, cwd=REPO)
    assert out.returncode == 0, f"stderr:\n{out.stderr[-4000:]}"
    return out.stdout


def test_small_mesh_train_lowering_compiles_with_shardings():
    out = run_sub("""
        import jax, jax.numpy as jnp, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import registry
        from repro.configs.base import RunConfig, ShapeConfig
        from repro.core.policy import ONLINE_BLOCK
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import model_zoo
        from repro.optim import adamw
        from repro.tools import roofline
        from repro.train import train_loop

        cfg = dataclasses.replace(registry.get_smoke("qwen2-7b"),
                                  n_heads=8, n_kv_heads=4)
        shape = ShapeConfig("t", 64, 8, "train")
        run = RunConfig(model=cfg, ft=ONLINE_BLOCK, attn_chunk=32)
        mesh = make_mesh((2, 4), ("data", "model"))
        with shd.use_mesh(mesh):
            mod = model_zoo.module_for(cfg)
            p_struct = jax.eval_shape(
                lambda: mod.init(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
            specs = shd.param_specs(p_struct)
            p_struct = jax.tree.map(
                lambda s, sp: jax.ShapeDtypeStruct(
                    s.shape, s.dtype, sharding=NamedSharding(mesh, sp)),
                p_struct, specs,
                is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
            opt_cfg = adamw.AdamWConfig()
            tc = train_loop.TrainConfig()
            o_struct = jax.eval_shape(
                lambda p: train_loop.init_opt_state(p, opt_cfg, tc),
                p_struct)
            b = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32,
                                                sharding=NamedSharding(
                                                    mesh, P("data"))),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32,
                                                sharding=NamedSharding(
                                                    mesh, P("data")))}
            step = train_loop.make_train_step(cfg, run, opt_cfg, tc)
            lowered = jax.jit(lambda p, o, bb, s: step(p, o, bb, s, None)
                              ).lower(p_struct, o_struct, b,
                                      jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
            mem = compiled.memory_analysis()
            cost = roofline.cost_dict(compiled)
            cb, per = roofline.collective_bytes(compiled.as_text())
        assert cost.get("flops", 0) > 0
        assert cb > 0, "sharded train step must contain collectives"
        print("OK flops", cost["flops"], "coll", cb, sorted(per))
    """)
    assert "OK" in out


def test_small_mesh_decode_lowering_compiles():
    out = run_sub("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import registry
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import model_zoo
        from repro.models.blocks import Ctx
        from repro.core.policy import ONLINE_BLOCK

        cfg = registry.get_smoke("zamba2-2.7b")
        mod = model_zoo.module_for(cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        with shd.use_mesh(mesh, {"seq": None}):
            p_struct = jax.eval_shape(
                lambda: mod.init(cfg, jax.random.PRNGKey(0), jnp.bfloat16))
            c_struct = jax.eval_shape(
                lambda: mod.init_cache(cfg, 8, 64, jnp.bfloat16))
            t = jax.ShapeDtypeStruct((8, 1), jnp.int32,
                                     sharding=NamedSharding(mesh,
                                                            P("data")))
            ctx = Ctx(ft=ONLINE_BLOCK, key=None, dtype=jnp.bfloat16)
            lowered = jax.jit(
                lambda p, tok, c: mod.decode_step(p, tok, c, cfg, ctx)
            ).lower(p_struct, t, c_struct)
            compiled = lowered.compile()
        from repro.tools import roofline
        print("OK", roofline.cost_dict(compiled).get("flops"))
    """)
    assert "OK" in out


def test_roofline_collective_parser():
    from repro.tools import roofline
    hlo = """
      %ag = bf16[16,512,128]{2,1,0} all-gather(%x), dimensions={0}
      %ar = f32[256,64]{1,0} all-reduce(%y), to_apply=%sum
      %rs = (f32[4,8]{1,0}, f32[4,8]{1,0}) reduce-scatter(%a, %b)
      %cp = u8[1024]{0} collective-permute(%z)
    """
    total, per = roofline.collective_bytes(hlo)
    assert per["all-gather"] == 16 * 512 * 128 * 2
    assert per["all-reduce"] == 256 * 64 * 4
    assert per["reduce-scatter"] == 2 * 4 * 8 * 4
    assert per["collective-permute"] == 1024
    assert total == sum(per.values())


def test_roofline_terms_and_bottleneck():
    from repro.tools import roofline
    rl = roofline.analyze({"flops": 197e12, "bytes accessed": 819e9 * 2},
                          "", model_flops_per_device=100e12)
    assert abs(rl.compute_s - 1.0) < 1e-6
    assert abs(rl.memory_s - 2.0) < 1e-6
    assert rl.bottleneck == "memory"
    assert abs(rl.useful_ratio - 100e12 / 197e12) < 1e-6


def test_peaks_table_keyed_by_device_kind():
    import pytest
    from repro.tools import roofline
    row = roofline.peaks("TPU v5 lite")
    assert row["bf16_flops"] == roofline.PEAK_FLOPS == 197e12
    assert row["hbm_bw"] == roofline.HBM_BW == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        roofline.peaks("cpu")
