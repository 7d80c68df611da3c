"""Distribution tests: run in a subprocess with 8 fake devices (jax pins the
device count at first init, so the main pytest process stays at 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str) -> str:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(REPO, "src") + ":" + REPO)
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env,
                         timeout=500)
    assert out.returncode == 0, out.stdout + "\n" + out.stderr
    return out.stdout


def test_moe_ep_shard_map_matches_local():
    """Expert-parallel (all_to_all) MoE == local dispatch, numerically."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.models import get_model
        from repro.distributed.sharding import mesh_context
        from repro.launch.mesh import make_debug_mesh

        cfg = get_smoke_config("deepseek-moe-16b").replace(
            moe_capacity_factor=8.0)  # no drops -> exact expert math
        m = get_model(cfg)
        params = m.init_params(jax.random.PRNGKey(0))
        tok = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                                 cfg.vocab_size)
        batch = {"tokens": tok, "labels": tok}
        _, met_local = jax.jit(m.loss_fn)(params, batch)
        mesh = make_debug_mesh(2, 2, pod=2)
        with mesh_context(mesh):
            _, met_ep = jax.jit(m.loss_fn)(params, batch)
        # nll must match exactly (same routing, no drops); the aux
        # load-balance term is a nonlinear function of per-block means and
        # legitimately differs between global and per-shard routing stats
        d = abs(float(met_local["nll"]) - float(met_ep["nll"]))
        assert d < 3e-4, (float(met_local["nll"]), float(met_ep["nll"]))
        print("EP-vs-local OK", d)
    """)
    assert "EP-vs-local OK" in out


def test_train_step_sharded_matches_unsharded():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.shapes import ShapeSpec
        from repro.launch.steps import lower_cell, make_train_step
        from repro.optim import AdamWConfig, adamw_init
        from repro.models import get_model
        from repro.data import SyntheticConfig, synthetic_batch

        cfg = get_smoke_config("chatglm3-6b")
        ocfg = AdamWConfig(lr=1e-3, warmup_steps=0, total_steps=4)
        model = get_model(cfg)
        dcfg = SyntheticConfig(vocab_size=cfg.vocab_size, seq_len=32,
                               batch_size=8)
        batch = synthetic_batch(dcfg, 0)
        params = model.init_params(jax.random.PRNGKey(0))
        state = adamw_init(params)
        step = make_train_step(cfg, ocfg)
        ref_state, ref_m = step(jax.tree.map(jnp.copy, state), batch)

        mesh = make_debug_mesh(2, 2, pod=2)
        from repro.distributed.sharding import mesh_context
        with mesh_context(mesh):
            sh_state, sh_m = jax.jit(step)(state, batch)
        assert abs(float(ref_m["loss"]) - float(sh_m["loss"])) < 2e-3
        for a, b in zip(jax.tree.leaves(ref_state["master"]),
                        jax.tree.leaves(sh_state["master"])):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=5e-3)
        print("sharded-train OK", float(sh_m["loss"]))
    """)
    assert "sharded-train OK" in out


def test_dryrun_cells_compile_on_debug_mesh():
    """lower+compile every step kind for three representative smoke archs."""
    out = _run("""
        from repro.configs import get_smoke_config
        from repro.launch.mesh import make_debug_mesh
        from repro.launch.shapes import ShapeSpec
        from repro.launch.steps import lower_cell
        from repro.launch import hlo_analysis

        mesh = make_debug_mesh(2, 2, pod=2)
        shapes = [ShapeSpec("t", 64, 8, "train"),
                  ShapeSpec("p", 64, 8, "prefill"),
                  ShapeSpec("d", 64, 8, "decode")]
        for arch in ("deepseek-v2-lite-16b", "hymba-1.5b",
                     "seamless-m4t-large-v2"):
            cfg = get_smoke_config(arch)
            for sh in shapes:
                lowered, _ = lower_cell(cfg, sh, mesh)
                c = lowered.compile()
                st = hlo_analysis.collective_stats(c.as_text())
                assert c.cost_analysis() is not None
        print("debug-mesh cells OK")
    """)
    assert "debug-mesh cells OK" in out


def test_compressed_crosspod_psum():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compress import compressed_psum_tree
        mesh = jax.make_mesh((2, 4), ("pod", "data"))
        g = {"w": jnp.arange(8.0).reshape(8, 1) * 1e-4}
        err = {"w": jnp.zeros((8, 1))}

        def f(g, e):
            return compressed_psum_tree(g, e, "pod")

        out, err2 = jax.jit(jax.shard_map(
            f, mesh=mesh,
            in_specs=(P("pod", None), P("pod", None)),
            out_specs=(P("pod", None), P("pod", None))))(g["w"], err["w"])
        # per-pod average of the two shards, up to int8 quantization error
        # (half an lsb: amax/127/2 ~ 2.8e-6 for these magnitudes)
        want = (np.asarray(g["w"][:4]) + np.asarray(g["w"][4:])) / 2
        np.testing.assert_allclose(np.asarray(out)[:4], want, atol=6e-6)
        print("compressed psum OK")
    """)
    assert "compressed psum OK" in out
