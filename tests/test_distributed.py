"""Distribution: sharding specs, multi-device pjit (subprocess), elastic
restore across mesh shapes, HLO analyzer."""
import os
import subprocess
import sys
import textwrap

import numpy as np

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_with_devices(script: str, n: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n}"
    env["PYTHONPATH"] = SRC
    # tests run on the CPU, and forced host devices are a CPU feature; a
    # child must never reach for a chip (only one process may hold it)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(script)],
        capture_output=True, text=True, env=env, timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_param_specs_resolve():
    import jax
    from jax.sharding import PartitionSpec as P

    from repro.configs import get_config
    from repro.distributed.sharding import ParallelConfig, param_specs
    from repro.launch.specs import abstract_params

    cfg = get_config("deepseek-v2-236b", reduced=True)
    params = abstract_params(cfg)
    specs = param_specs(cfg, params)
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    joined = {"/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp): s
              for kp, s in flat}
    # stacked group leaves get a leading None for the scan dim
    moe_w1 = [s for p, s in joined.items() if p.endswith("moe/w1")]
    assert moe_w1 and moe_w1[0][1] == "__M__"  # experts over tensor axis
    wq = [s for p, s in joined.items() if p.endswith("mixer/wuq")]
    # column-parallel: output dim jointly (fsdp, tensor)-sharded
    assert wq and wq[0][1] is None and wq[0][2] == "__FM__"


def test_pjit_train_step_multidevice_matches_single():
    """Same loss on a (2,2,2) mesh as on 1 device — SPMD correctness."""
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import init_params
        from repro.optim.adamw import AdamWConfig, adamw_init
        from repro.train.step import make_train_step
        from repro.distributed.sharding import (ParallelConfig, param_specs,
            batch_specs, make_shardings)
        from repro.distributed.ctx import activation_sharding

        cfg = get_config("llama3-8b", reduced=True)
        import dataclasses
        cfg = dataclasses.replace(cfg, compute_dtype="float32")
        oc = AdamWConfig(lr=1e-3)
        pc = ParallelConfig(compress_grads=False)
        params = init_params(cfg, jax.random.PRNGKey(0))
        opt = adamw_init(params, oc)
        key = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(key, (8, 16), 0, cfg.vocab_size),
                 "labels": jax.random.randint(key, (8, 16), 0, cfg.vocab_size)}
        step = make_train_step(cfg, oc, pc)

        # single device reference
        p1, o1, m1 = jax.jit(step)(params, opt, batch, jnp.int32(0))

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        ps = make_shardings(mesh, pc, param_specs(cfg, params))
        os_ = {"mu": ps, "nu": ps, "count": NamedSharding(mesh, P())}
        bs = make_shardings(mesh, pc, batch_specs(cfg, batch))
        with activation_sharding(mesh, pc):
            jstep = jax.jit(step, in_shardings=(ps, os_, bs, NamedSharding(mesh, P())),
                            out_shardings=(ps, os_, None))
            pd = jax.device_put(params, ps)
            od = jax.device_put(opt, os_)
            bd = jax.device_put(batch, bs)
            p2, o2, m2 = jstep(pd, od, bd, jnp.int32(0))
        print("LOSS1", float(m1["loss"]))
        print("LOSS2", float(m2["loss"]))
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-3
        # updated params agree
        l1 = np.asarray(jax.tree.leaves(p1)[0], np.float32)
        l2 = np.asarray(jax.device_get(jax.tree.leaves(p2)[0]), np.float32)
        np.testing.assert_allclose(l1, l2, rtol=1e-3, atol=1e-4)
        print("OK")
    """)
    assert "OK" in out


def test_moe_ep_multidevice_matches_single():
    out = run_with_devices("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.launch.mesh import make_mesh
        from repro.models import init_params, forward_train
        from repro.distributed.sharding import (ParallelConfig, param_specs,
            batch_specs, make_shardings)
        from repro.distributed.ctx import activation_sharding

        cfg = get_config("deepseek-v2-236b", reduced=True)
        cfg = dataclasses.replace(cfg, compute_dtype="float32",
            moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))
        params = init_params(cfg, jax.random.PRNGKey(0))
        key = jax.random.PRNGKey(1)
        batch = {"tokens": jax.random.randint(key, (4, 16), 0, cfg.vocab_size)}
        ref, _ = jax.jit(lambda p, b: forward_train(p, cfg, b))(params, batch)

        pc = ParallelConfig()
        mesh = make_mesh((2, 4), ("data", "model"))
        ps = make_shardings(mesh, pc, param_specs(cfg, params))
        bs = make_shardings(mesh, pc, batch_specs(cfg, batch))
        with activation_sharding(mesh, pc):
            f = jax.jit(lambda p, b: forward_train(p, cfg, b),
                        in_shardings=(ps, bs))
            got, _ = f(jax.device_put(params, ps), jax.device_put(batch, bs))
        np.testing.assert_allclose(np.asarray(jax.device_get(got), np.float32),
                                   np.asarray(ref, np.float32),
                                   rtol=2e-3, atol=2e-3)
        print("OK")
    """)
    assert "OK" in out


def test_elastic_checkpoint_across_mesh_shapes(tmp_path):
    """Save sharded on a (4,2) mesh; restore onto (2,4) and 1-device."""
    out = run_with_devices(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import save_checkpoint, restore_checkpoint
        from repro.launch.mesh import make_mesh

        tree = {{"w": jnp.arange(64.0).reshape(8, 8)}}
        mesh_a = make_mesh((4, 2), ("data", "model"))
        sh_a = {{"w": NamedSharding(mesh_a, P("data", "model"))}}
        t_a = jax.device_put(tree, sh_a)
        save_checkpoint(r"{tmp_path}", 3, t_a)

        mesh_b = make_mesh((2, 4), ("data", "model"))
        sh_b = {{"w": NamedSharding(mesh_b, P("model", "data"))}}
        t_b, step, _ = restore_checkpoint(r"{tmp_path}", tree, shardings=sh_b)
        assert step == 3
        assert t_b["w"].sharding == sh_b["w"]
        np.testing.assert_array_equal(np.asarray(jax.device_get(t_b["w"])),
                                      np.asarray(tree["w"]))
        print("OK")
    """)
    assert "OK" in out


def test_dryrun_cells_on_small_mesh():
    """build_cell lowers+compiles train/prefill/decode for three families."""
    out = run_with_devices("""
        import jax, dataclasses
        from repro.configs import get_config, SHAPES
        from repro.launch.dryrun import build_cell
        from repro.launch.mesh import make_mesh
        from repro.distributed.sharding import ParallelConfig
        from repro.distributed.ctx import activation_sharding
        pc = ParallelConfig()
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        for arch in ["llama3-8b", "jamba-1.5-large-398b", "seamless-m4t-large-v2"]:
            cfg = get_config(arch, reduced=True)
            for shape_name in ["train_4k", "prefill_32k", "decode_32k"]:
                shape = dataclasses.replace(SHAPES[shape_name], seq_len=32,
                                            global_batch=8)
                with activation_sharding(mesh, pc):
                    jitted, args = build_cell(cfg, shape, mesh, pc)
                    jitted.lower(*args).compile()
                print("OK", arch, shape_name)
    """)
    assert out.count("OK") == 9


def test_hlo_analyzer_counts_scan_trips():
    out = run_with_devices("""
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.hlo_stats import analyze_hlo
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        w = jax.ShapeDtypeStruct((4, 256, 256), jnp.bfloat16)
        x = jax.ShapeDtypeStruct((64, 256), jnp.bfloat16)
        def f(w, x):
            def body(x, wi):
                return jnp.tanh(x @ wi), None
            y, _ = jax.lax.scan(body, x, w)
            return y
        c = jax.jit(f, in_shardings=(NamedSharding(mesh, P(None, "data", "model")),
                                     NamedSharding(mesh, P(None, "model")))
                    ).lower(w, x).compile()
        r = analyze_hlo(c.as_text())
        expect = 2 * 64 * 256 * 256 * 4 / 8  # per-device, x4 layers
        assert abs(r["flops"] - expect) / expect < 0.05, r["flops"]
        ag = r["collectives"].get("all-gather", {"count": 0})
        assert ag["count"] == 4, ag  # one per scan iteration
        print("OK")
    """, n=8)
    assert "OK" in out


def test_sharded_staged_spmv_matches_single_on_8_devices():
    """Acceptance: on 8 forced host devices the shard_map-staged SpMV/SpMM
    match the single-device kernel within 1e-6 and the partitioner keeps
    the worst shard <= 1.5x the mean nnz."""
    out = run_with_devices("""
        import numpy as np, jax, jax.numpy as jnp
        from repro.core import vbr as vbrlib
        from repro.core.staging import stage_spmv, stage_spmm
        from repro.launch.mesh import make_staging_mesh

        v = vbrlib.synthesize(360, 320, 20, 16, 80, block_sparsity=0.25,
                              uniform=False, seed=7)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(v.shape[1]).astype(np.float32))
        val = jnp.asarray(v.val)
        ref = stage_spmv(v)(val, x)

        mesh = make_staging_mesh(8)
        kern = stage_spmv(v, mesh=mesh)
        assert kern.imbalance() <= 1.5, kern.imbalance()
        got = jax.device_get(kern(val, x))
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   atol=1e-6, rtol=1e-6)

        X = jnp.asarray(rng.standard_normal((v.shape[1], 8)).astype(np.float32))
        refm = stage_spmm(v, 8)(val, X)
        gotm = jax.device_get(stage_spmm(v, 8, mesh=mesh)(val, X))
        np.testing.assert_allclose(np.asarray(gotm), np.asarray(refm),
                                   atol=1e-6, rtol=1e-6)
        print("OK", float(kern.imbalance()))
    """)
    assert "OK" in out


def test_mesh2d_spmm_matches_1d_and_unsharded_with_warm_restart(tmp_path):
    """Acceptance (ISSUE 5): on 8 forced host devices, 2-D (shards x
    model) SpMM — overlapped-gather path enabled — matches the 1-D mesh
    and unsharded kernels within 1e-6, per-shard autotune plans are keyed
    with the model column count, and a warm restart re-stages with ZERO
    new plan files.  sparse_matmul_auto accepts the same 2-D mesh."""
    out = run_with_devices(f"""
        import os, numpy as np, jax, jax.numpy as jnp
        os.environ["REPRO_CACHE_DIR"] = r"{tmp_path}"
        from repro.core import vbr as vbrlib
        from repro.core.staging import StagingOptions, clear_cache, stage_spmm
        from repro.launch.mesh import make_staging_mesh

        v = vbrlib.synthesize(160, 140, 12, 10, 36, block_sparsity=0.25,
                              uniform=False, seed=7)
        rng = np.random.default_rng(0)
        X = jnp.asarray(rng.standard_normal((v.shape[1], 8)).astype(np.float32))
        val = jnp.asarray(v.val)
        ref = np.asarray(stage_spmm(v, 8)(val, X))

        ref1d = np.asarray(jax.device_get(
            stage_spmm(v, 8, mesh=make_staging_mesh(4))(val, X)))
        np.testing.assert_allclose(ref1d, ref, atol=1e-6, rtol=1e-6)

        # (2,4) with the default backend: pure 2-D equivalence
        kern24 = stage_spmm(v, 8, mesh=make_staging_mesh((2, 4)))
        assert kern24.overlap_gather and kern24.model_size == 4
        got24 = np.asarray(jax.device_get(kern24(val, X)))
        np.testing.assert_allclose(got24, ref, atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(got24, ref1d, atol=1e-6, rtol=1e-6)

        # (4,2) with autotune: per-shard plans keyed by model cols
        opts = StagingOptions(backend="autotune")
        mesh = make_staging_mesh((4, 2))
        kern = stage_spmm(v, 8, opts, mesh=mesh)  # overlap_gather on
        assert kern.overlap_gather and kern.model_size == 2
        got = np.asarray(jax.device_get(kern(val, X)))
        np.testing.assert_allclose(got, ref, atol=1e-6, rtol=1e-6)
        np.testing.assert_allclose(got, ref1d, atol=1e-6, rtol=1e-6)

        plans = os.path.join(r"{tmp_path}", "plans")
        names = set(os.listdir(plans))
        mc = [n for n in names if "-mc" in n]
        assert len(mc) == 4, mc  # one plan per shard, keyed by model cols

        # warm restart: fresh staging, zero new plan files
        clear_cache()
        kern = stage_spmm(v, 8, opts, mesh=make_staging_mesh((4, 2)))
        got = np.asarray(jax.device_get(kern(val, X)))
        np.testing.assert_allclose(got, ref, atol=1e-6, rtol=1e-6)
        assert set(os.listdir(plans)) == names, "warm restart re-benchmarked"

        # sparse_matmul_auto end-to-end on the same 2-D mesh
        from repro.sparse import linear
        pat = linear.random_pattern(64, 96, 8, 8, density=0.4)
        tiles = jnp.asarray(rng.standard_normal(
            (pat.n_tiles, 8, 8)).astype(np.float32))
        xs = jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32))
        mesh = make_staging_mesh((2, 4))
        dense_ref = np.asarray(linear.sparse_matmul(xs, tiles, pat))
        got = np.asarray(jax.device_get(jax.jit(
            lambda a, t: linear.sparse_matmul_auto(
                a, t, pat, mesh=mesh, out_model=True))(xs, tiles)))
        np.testing.assert_allclose(got, dense_ref, atol=1e-5, rtol=1e-5)
        print("OK")
    """)
    assert "OK" in out


def test_fetch_and_constrain_noop_outside_context():
    """Model code must run unchanged without an activation_sharding ctx."""
    import jax.numpy as jnp

    from repro.distributed.ctx import DP, MODEL, constrain, fetch

    x = jnp.ones((4, 8))
    assert constrain(x, DP, None) is x
    assert fetch(x, None, MODEL) is x
