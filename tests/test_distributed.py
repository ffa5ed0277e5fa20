"""Distributed behaviour tests.

Device count is locked at first JAX init, so multi-device tests run in
subprocesses with XLA_FLAGS=--xla_force_host_platform_device_count=8
(mesh 2×4 over ("data","model")) — pjit-sharded train step, sharding-rule
consistency, elastic checkpoint resharding 8→4 devices.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_sub(body: str, devices: int = 8, timeout: int = 420) -> str:
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


def test_pjit_train_step_shards_and_matches_single_device():
    """One ABFT-protected train step under a 2×4 mesh: loss finite, params
    sharded per the rules, loss equal to the unsharded run."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding
        from repro.configs import registry
        from repro.configs.base import RunConfig
        from repro.core.policy import ONLINE_BLOCK
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_mesh
        from repro.models import model_zoo
        from repro.optim import adamw
        from repro.train import train_loop

        cfg = registry.get_smoke("qwen2-7b")
        mod = model_zoo.module_for(cfg)
        run = RunConfig(model=cfg, ft=ONLINE_BLOCK, dtype="float32",
                        attn_chunk=32)
        opt_cfg = adamw.AdamWConfig(lr=1e-3)
        tc = train_loop.TrainConfig(total_steps=10, warmup_steps=1)
        params = mod.init(cfg, jax.random.PRNGKey(0), jnp.float32)
        opt = train_loop.init_opt_state(params, opt_cfg, tc)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                                         cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(1), (8, 64), 0,
                                         cfg.vocab_size),
        }
        step = train_loop.make_train_step(cfg, run, opt_cfg, tc)
        # single-device reference
        _, _, m_ref = jax.jit(step)(params, opt, batch, jnp.asarray(0), None)
        ref = float(m_ref["loss"])

        mesh = make_mesh((2, 4), ("data", "model"))
        with shd.use_mesh(mesh):
            specs = shd.param_specs(params)
            p_sh = jax.device_put(params, jax.tree.map(
                lambda s: NamedSharding(mesh, s), specs,
                is_leaf=lambda x: isinstance(
                    x, jax.sharding.PartitionSpec)))
            o_sh = jax.device_put(opt, None)
            b_sh = jax.device_put(batch, NamedSharding(
                mesh, jax.sharding.PartitionSpec("data")))
            new_p, _, metrics = jax.jit(step)(p_sh, o_sh, b_sh,
                                              jnp.asarray(0), None)
            loss = float(metrics["loss"])
        # params actually sharded over the mesh
        wq = new_p["layers"]["attn"]["wq"]
        n_shards = len(set(d for d in wq.sharding.device_set))
        print("LOSS", loss, "REF", ref, "SHARDS", n_shards)
        assert n_shards > 1
        assert abs(loss - ref) < 1e-3
    """)
    assert "LOSS" in out


def test_ft_adds_no_collectives():
    """DESIGN.md §2.2: ABFT checksums inherit operand shardings — enabling
    FT must not add collective ops to the partitioned HLO."""
    out = run_sub("""
        import jax, jax.numpy as jnp, re
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import ft_dot
        from repro.core.policy import ONLINE_BLOCK, FT_OFF
        from repro.launch.mesh import make_mesh
        from repro.tools import roofline

        mesh = make_mesh((2, 4), ("data", "model"))
        x = jax.ShapeDtypeStruct((256, 512), jnp.float32,
                                 sharding=NamedSharding(mesh, P("data")))
        w = jax.ShapeDtypeStruct((512, 384), jnp.float32,
                                 sharding=NamedSharding(mesh,
                                                        P(None, "model")))

        def collectives(ft):
            fn = lambda x, w: ft_dot(x, w, ft=ft)
            hlo = jax.jit(fn).lower(x, w).compile().as_text()
            _, per = roofline.collective_bytes(hlo)
            return per

        with mesh:
            off = collectives(FT_OFF)
            on = collectives(ONLINE_BLOCK)
        print("OFF", off, "ON", on)
        # FT may add only sub-kilobyte scalar reductions (threshold/verdict),
        # never operand-scale collectives
        extra = sum(on.values()) - sum(off.values())
        assert extra < 64 * 1024, (off, on)
    """)
    assert "ON" in out


def test_checkpoint_elastic_reshard_8_to_4():
    """Save under an 8-device mesh, restore under a 4-device mesh."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.ckpt import Checkpointer
        from repro.launch.mesh import make_mesh

        tree = {"w": jnp.arange(64.0).reshape(8, 8)}
        mesh8 = make_mesh((8,), ("data",))
        sh8 = {"w": NamedSharding(mesh8, P("data"))}
        tree8 = jax.device_put(tree, sh8)
        d = tempfile.mkdtemp()
        ck = Checkpointer(d)
        ck.save(5, tree8)

        devs = np.array(jax.devices()[:4]).reshape(4)
        mesh4 = jax.sharding.Mesh(devs, ("data",))
        sh4 = {"w": NamedSharding(mesh4, P("data"))}
        restored, step, _ = ck.restore(tree, shardings=sh4)
        assert step == 5
        assert restored["w"].sharding.num_devices == 4
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64.0).reshape(8, 8))
        print("RESHARD OK")
    """)


def test_mesh_construction():
    out = run_sub("""
        from repro.launch.mesh import make_production_mesh
        import jax
        # 8 host devices can't build 256; just validate axis plumbing via a
        # tiny replica of the production mesh builder
        m = jax.make_mesh((2, 4), ("data", "model"))
        assert m.axis_names == ("data", "model")
        m2 = jax.make_mesh((2, 2, 2), ("pod", "data", "model"))
        assert m2.axis_names == ("pod", "data", "model")
        print("MESH OK", m.devices.shape, m2.devices.shape)
    """)
    assert "MESH OK" in out


def test_sharding_rule_rank_mismatch_raises():
    """A PARAM_RULES entry whose rank disagrees with the array must raise —
    the pre-PR-5 behaviour silently replicated (de-sharded) the weight,
    turning a sharding-rule typo into an invisible perf regression."""
    from repro.distributed import sharding

    # sane paths still resolve
    spec = sharding.spec_for_path("layers/attn/wq", ndim=2)
    assert len(spec) == 2
    # stacked leading dim is filled with None, not an error
    spec3 = sharding.spec_for_path("layers/attn/wq", ndim=3, n_stacked=1)
    assert len(spec3) == 3 and spec3[0] is None
    # rank mismatch (rule names more dims than the array has) raises loudly
    with pytest.raises(ValueError, match="attn.*wq"):
        sharding.spec_for_path("layers/attn/wq", ndim=1)
    with pytest.raises(ValueError, match="de-shard"):
        sharding.spec_for_path("moe/w_gate", ndim=2)
