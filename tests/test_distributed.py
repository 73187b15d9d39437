"""Multi-device tests (8 host devices via subprocess): sharded training step,
elastic checkpoint restore across topologies, distributed lineage scans."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parent.parent / "src")


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True, env=env,
        timeout=560,
    )
    assert out.returncode == 0, f"STDOUT:\n{out.stdout}\nSTDERR:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    out = run_sub(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import smoke_config
        from repro.launch.steps import build_train
        from repro.launch.mesh import make_host_mesh
        from repro.models import model as M
        from repro.models.config import ShapeConfig
        from repro.optim import adamw

        cfg = smoke_config("llama3.2-3b")
        shape = ShapeConfig("t", 32, 8, "train")
        opt_cfg = adamw.AdamWConfig(lr=1e-3, warmup_steps=1)
        rng = np.random.default_rng(0)
        toks = rng.integers(0, cfg.vocab, (8, 32)).astype(np.int32)
        batch = {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)}
        params, _ = M.init(cfg, jax.random.PRNGKey(0))
        opt = adamw.init(params, opt_cfg)

        # single-device reference FIRST (the sharded step donates its args)
        from repro.launch.steps import make_train_step
        step = jax.jit(make_train_step(cfg, opt_cfg))
        p1, o1, m1 = step(params, opt, batch)
        loss_single = float(m1["loss"])

        mesh = make_host_mesh(data=4, model=2)
        with mesh:
            jitted, _ = build_train(mesh, cfg, shape, opt_cfg, fsdp=True)
            p2, o2, m2 = jitted(params, opt, batch)
        loss_sharded = float(m2["loss"])
        assert abs(loss_sharded - loss_single) < 2e-2, (loss_sharded, loss_single)
        # parameters evolve identically (up to bf16 noise)
        d = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))), p1, p2)
        mx = max(jax.tree.leaves(d))
        assert mx < 5e-2, mx
        print("SHARDED_OK", loss_sharded)
    """))
    assert "SHARDED_OK" in out


def test_elastic_checkpoint_reshard():
    out = run_sub(textwrap.dedent("""
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint.manager import CheckpointManager
        from repro.launch.mesh import make_host_mesh

        tree = {"w": np.arange(256, dtype=np.float32).reshape(16, 16)}
        with tempfile.TemporaryDirectory() as d:
            # save from a (4,2) topology
            mesh_a = make_host_mesh(data=4, model=2)
            sh_a = {"w": NamedSharding(mesh_a, P("data", "model"))}
            placed = jax.device_put(tree["w"], sh_a["w"])
            cm = CheckpointManager(d)
            cm.save(3, {"w": placed})
            # restore onto a (2,4) topology — elastic reshard
            mesh_b = make_host_mesh(data=2, model=4)
            sh_b = {"w": NamedSharding(mesh_b, P("data", "model"))}
            step, restored = cm.restore(tree, shardings=sh_b)
            assert step == 3
            np.testing.assert_array_equal(np.asarray(restored["w"]), tree["w"])
            assert restored["w"].sharding.is_equivalent_to(sh_b["w"], 2)
        print("ELASTIC_OK")
    """))
    assert "ELASTIC_OK" in out


def test_distributed_lineage_matches_local():
    out = run_sub(textwrap.dedent("""
        import numpy as np
        import jax
        from repro.tpch import generate, ALL_QUERIES
        from repro.core import PredTrace
        from repro.core.distributed import distributed_refine
        from repro.launch.mesh import make_host_mesh

        db = generate(sf=0.002, seed=1)
        mesh = make_host_mesh(data=8, model=1)
        for q in ("q3", "q4", "q12"):
            plan = ALL_QUERIES[q](db)
            pt = PredTrace(db, plan)
            pt.infer_iterative(); pt.run_unmodified()
            if pt.exec_result.output.nrows == 0:
                continue
            local = pt.query_iterative(0)
            binding = pt._output_binding(0)
            dist = distributed_refine(pt.iter_plan, db, binding, mesh)
            for tab in set(local.lineage) | set(dist.lineage):
                a = set(local.lineage.get(tab, np.array([])).tolist())
                b = set(dist.lineage.get(tab, np.array([])).tolist())
                assert a == b, (q, tab, len(a), len(b))
        print("DIST_LINEAGE_OK")
    """))
    assert "DIST_LINEAGE_OK" in out


def test_multipod_mesh_lowering_smoke():
    """A reduced model lowers+compiles on a (pod,data,model) host mesh."""
    out = run_sub(textwrap.dedent("""
        import jax
        from repro.configs import smoke_config
        from repro.launch.mesh import make_host_mesh
        from repro.launch.steps import build_train
        from repro.models.config import ShapeConfig
        from repro.optim import adamw

        cfg = smoke_config("mixtral-8x22b")
        shape = ShapeConfig("t", 32, 8, "train")
        mesh = make_host_mesh(pod=2, data=2, model=2)
        with mesh:
            jitted, (p, o, b) = build_train(mesh, cfg, shape, adamw.AdamWConfig(), fsdp=True)
            compiled = jitted.lower(p, o, b).compile()
        txt = compiled.as_text()
        assert "all-reduce" in txt or "all-gather" in txt
        print("MULTIPOD_OK")
    """))
    assert "MULTIPOD_OK" in out


def test_mesh_scan_is_int32_exact():
    """The mesh path holds int32 on the device: a padded IN set must not
    match key-0 rows, a float64 column must not be compared in float32, and
    the columns must really be spread over every device."""
    out = run_sub(textwrap.dedent("""
        import numpy as np
        from repro.core import ScanEngine
        from repro.core.distributed import PartitionExecutor
        from repro.core.expr import Col, IsIn, Param, land
        from repro.core.table import Table
        from repro.launch.mesh import make_host_mesh

        n = 4000
        k = (np.arange(n) % 50).astype(np.int64)         # key 0 present
        price = 1000.0 + np.arange(n) * 1e-6             # float32 merges these
        t = Table({"k": k, "price": price}, {}, "t")
        eng = ScanEngine()
        ex = PartitionExecutor(eng, mesh=make_host_mesh(data=4, model=1),
                               max_workers=0)
        s = np.array([3, 7, 9], np.int64)                # pads to 4 slots
        m = ex.scan(IsIn(Col("k"), Param("s")), t, {"s": s})
        assert np.array_equal(m, np.isin(k, s)) and not m[k == 0].any()
        m = ex.scan(Col("k").eq(Param("s")), t, {"s": np.array([], np.int64)})
        assert not m.any()
        assert eng.stats.mesh_scans == 2
        p = float(price[1234])
        pred = land(Col("price") < Param("p"), Col("k") >= Param("q"))
        m = ex.scan(pred, t, {"p": p, "q": 1})
        assert np.array_equal(m, (price < p) & (k >= 1))
        assert eng.stats.mesh_scans == 2, "float64 compare reached the device"
        assert ex.placement() == {"t": {0, 1, 2, 3}}, ex.placement()
        print("MESH_EXACT_OK")
    """), devices=4)
    assert "MESH_EXACT_OK" in out
