"""Distribution tests that need >1 device: run in a subprocess with
XLA_FLAGS=--xla_force_host_platform_device_count=8 (the dry-run pattern;
the main test process keeps its single CPU device)."""
import os
import subprocess
import sys
import textwrap

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(body: str, devices: int = 8, timeout: int = 600) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
    """) + textwrap.dedent(body)
    env = dict(os.environ,
               PYTHONPATH=os.path.abspath(REPO_SRC))
    env.pop("XLA_FLAGS", None)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=env)
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_pipeline_spmd_matches_reference():
    """GPipe-style ppermute pipeline == sequential oracle (core/pipeline.py
    — the Eq. 1 double-buffer as a collective schedule)."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import pipeline_spmd, pipeline_reference
        mesh = jax.make_mesh((4,), ("stage",))
        def stage_fn(p, x):
            return jnp.tanh(x @ p["w"] + p["b"])
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (4, 16, 16)) * 0.5,
                  "b": jnp.zeros((4, 16))}
        mb = jax.random.normal(key, (8, 16))
        got = pipeline_spmd(stage_fn, params, mb, mesh, axis="stage")
        want = pipeline_reference(stage_fn, params, mb)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)
        print("pipeline OK")
    """)


def test_train_step_pjit_small_mesh():
    """Full sharded train step on a 4x2 (data, model) mesh: loss finite,
    params updated, batch actually sharded."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import smoke_config
        from repro.models import init_params
        from repro.optim.adamw import AdamWConfig, init_opt_state
        from repro.train import TrainOptions, make_train_step
        from repro.train import sharding as shd
        from repro.data import DataConfig, SyntheticLM

        from repro.launch.mesh import make_test_mesh
        cfg = smoke_config("granite-8b")
        mesh = make_test_mesh((4, 2))
        key = jax.random.PRNGKey(0)
        params = init_params(key, cfg)
        opt = init_opt_state(params)
        data = SyntheticLM(DataConfig(vocab=cfg.vocab, seq_len=32, global_batch=8))
        batch = {k: jnp.asarray(v) for k, v in data.batch(0).items()}

        p_specs, dropped = shd.param_specs(params, mesh)
        b_specs = shd.batch_specs(batch, mesh)
        o_specs = {"m": p_specs, "v": p_specs, "count": P()}
        step = make_train_step(cfg, AdamWConfig(lr=1e-3), TrainOptions())
        with mesh:
            jstep = jax.jit(step, in_shardings=jax.tree.map(
                lambda s: NamedSharding(mesh, s), (p_specs, o_specs, b_specs),
                is_leaf=lambda x: isinstance(x, P)))
            p2, o2, m = jstep(params, opt, batch)
        assert np.isfinite(float(m["loss"]))
        # embedding really is vocab-sharded over `model`
        emb = p2["embed"]["w"]
        assert len(emb.addressable_shards) == 8
        shard_rows = emb.addressable_shards[0].data.shape[0]
        assert shard_rows == emb.shape[0] // 2, (shard_rows, emb.shape)
        print("pjit train step OK, loss", float(m["loss"]))
    """)


def test_dryrun_cell_mini_mesh():
    """The dry-run machinery end-to-end on an 8-chip (4 data x 2 model)
    mini-mesh: lower+compile+cost+collectives for one arch x shape."""
    run_sub("""
        import jax, numpy as np, json
        from jax.sharding import NamedSharding, PartitionSpec as P
        import repro.launch.dryrun as dr
        from repro.configs import smoke_config

        from repro.launch.mesh import make_test_mesh
        mesh = make_test_mesh((4, 2))
        cfg = smoke_config("granite-8b")
        import repro.configs.base as base
        # shrink the global shape table for the mini run
        orig = dict(base.SHAPES)
        base.SHAPES["train_4k"] = (64, 8)
        dr.SHAPES["train_4k"] = (64, 8)
        fn, args, shardings, dropped = dr.build_cell(cfg, "train_4k", mesh)
        with mesh:
            in_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), shardings,
                                 is_leaf=lambda x: isinstance(x, P))
            lowered = jax.jit(fn, in_shardings=in_sh).lower(*args)
            compiled = lowered.compile()
            cost = compiled.cost_analysis()
            coll = dr.parse_collective_bytes(compiled.as_text())
        assert cost.get("flops", 0) > 0
        assert coll["total"] > 0, "SPMD program must contain collectives"
        print("mini dryrun OK", json.dumps({k: v for k, v in coll.items()}))
    """)


def test_multipod_mesh_axes():
    run_sub("""
        from repro.launch.mesh import make_production_mesh
        m = make_production_mesh(multi_pod=True)
        assert dict(m.shape) == {"pod": 2, "data": 16, "model": 16}
        m2 = make_production_mesh()
        assert dict(m2.shape) == {"data": 16, "model": 16}
        print("mesh OK")
    """, devices=512)


def test_lm_pipeline_parallel_matches_reference():
    """Transformer blocks as pipeline stages (ppermute schedule) == the
    sequential oracle — LM-side pipeline parallelism end to end."""
    run_sub("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs import smoke_config
        from repro.models import init_params
        from repro.graphs.lm_pipeline import (pipeline_forward,
                                              pipeline_forward_reference)
        cfg = dataclasses.replace(smoke_config("granite-8b"), n_layers=4)
        key = jax.random.PRNGKey(0)
        params = init_params(key, cfg)
        mesh = jax.make_mesh((4,), ("stage",))
        toks = jax.random.randint(key, (6, 16), 0, cfg.vocab)  # 6 microbatches
        got = pipeline_forward(params, cfg, toks, mesh, n_stages=4)
        want = pipeline_forward_reference(params, cfg, toks, n_stages=4)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=3e-2, atol=3e-2)
        print("LM pipeline OK", got.shape)
    """)
