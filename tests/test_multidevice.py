"""Multi-device behaviour via subprocesses (XLA_FLAGS host device count).

These run the real shard_map/pjit paths on 8 simulated devices: distributed
mining parity, EP-MoE parity vs single device, elastic checkpoint reshard,
and a miniature dry-run through the production launcher code path.
"""

import json
import os
import subprocess
import sys
import textwrap

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_py(code: str, n_devices: int = 8, timeout: int = 480):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=timeout, env=env)
    assert r.returncode == 0, r.stdout + "\n" + r.stderr
    return r.stdout


def test_mining_parity_on_8_devices():
    out = run_py("""
        import numpy as np, json
        from repro.core import mine, sequential_apriori
        rng = np.random.default_rng(0)
        base = rng.random((4, 20)) < 0.4
        txns = []
        for _ in range(160):
            pat = base[rng.integers(4)]
            row = np.where(rng.random(20) < 0.85, pat, rng.random(20) < 0.1)
            t = np.nonzero(row)[0].tolist() or [0]
            txns.append(t)
        oracle = sequential_apriori(txns, 0.3)
        import jax
        assert len(jax.devices()) == 8
        for algo in ["spc", "optimized_vfpc"]:
            res = mine(txns, n_items=20, min_sup=0.3, algorithm=algo)
            assert res.itemsets() == oracle, algo
        print("PARITY_OK")
    """)
    assert "PARITY_OK" in out


def test_ep_moe_matches_single_device():
    out = run_py("""
        import jax, numpy as np, dataclasses
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.models.moe import moe_init, moe_apply, _moe_apply_global
        from repro.models.model import ShardCtx
        from repro import sharding
        from repro.compat import make_mesh
        cfg = dataclasses.replace(get_config("qwen3-moe-30b-a3b", smoke=True),
                                  capacity_factor=8.0)
        p, _ = moe_init(jax.random.PRNGKey(0), cfg)
        mesh = make_mesh((2, 4), ("data", "model"))
        ctx = ShardCtx(mesh, sharding.make_rules())
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, cfg.d_model),
                              jnp.float32).astype(jnp.bfloat16)
        y_ep, aux_ep = jax.jit(lambda p, x: moe_apply(p, x, cfg, ctx))(p, x)
        y_g, aux_g = jax.jit(lambda p, x: _moe_apply_global(p, x, cfg, None))(p, x)
        err = float(jnp.max(jnp.abs(y_ep.astype(jnp.float32) - y_g.astype(jnp.float32))))
        scale = float(jnp.max(jnp.abs(y_g.astype(jnp.float32)))) + 1e-9
        assert err / scale < 0.05, (err, scale)
        print("EP_OK", err/scale)
    """)
    assert "EP_OK" in out


def test_elastic_reshard_8_to_4():
    out = run_py("""
        import jax, os, tempfile, numpy as np
        from repro.configs import get_config
        from repro.models import build_model
        from repro.optim import AdamWConfig
        from repro.train import init_train_state, save_checkpoint
        from repro.train.elastic import restore_elastic
        from repro import sharding
        from repro.compat import make_mesh
        model = build_model(get_config("smollm-135m", smoke=True))
        opt = AdamWConfig()
        rules = sharding.make_rules()
        mesh8 = make_mesh((4, 2), ("data", "model"))
        state = init_train_state(model, opt, jax.random.PRNGKey(0), mesh8, rules)
        d = tempfile.mkdtemp()
        save_checkpoint(d, 5, state)
        # restore onto a DIFFERENT mesh (2x2 = "scale down to 4 devices")
        mesh4 = make_mesh((2, 2), ("data", "model"))
        tmpl = jax.tree.map(lambda x: x, state)
        state4, step = restore_elastic(d, model, opt, mesh4, rules, tmpl)
        assert step == 5
        a = np.asarray(jax.device_get(state["params"]["embed"]["table"]), np.float32)
        b = np.asarray(jax.device_get(state4["params"]["embed"]["table"]), np.float32)
        assert (a == b).all()
        print("ELASTIC_OK")
    """)
    assert "ELASTIC_OK" in out


def test_mini_dryrun_multipod_codepath():
    """The production dryrun code path on a small mesh: lower+compile train
    and decode for a smoke arch on (pod, data, model) axes."""
    out = run_py("""
        import jax, jax.numpy as jnp
        from repro.configs import get_config, ShapeConfig
        from repro.models import build_model
        from repro import sharding
        from repro.launch.dryrun import build_step
        from repro.compat import make_mesh
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = sharding.make_rules()
        model = build_model(get_config("smollm-135m", smoke=True))
        for shape in [ShapeConfig("t", 32, 8, "train"),
                      ShapeConfig("p", 32, 8, "prefill"),
                      ShapeConfig("d", 64, 8, "decode")]:
            fn, ex, _, _ = build_step(model, shape, mesh, rules)
            compiled = fn.lower(*ex).compile()
            assert compiled.memory_analysis() is not None
        print("MINIDRY_OK")
    """)
    assert "MINIDRY_OK" in out


def test_2d_candidate_decomposition():
    """Beyond-paper: candidates sharded over `model` while transactions shard
    over `data` (2-D MapReduce decomposition) — identical results."""
    out = run_py("""
        import jax, numpy as np
        from repro.core import mine, sequential_apriori
        from repro.core.mapreduce import MapReduceRuntime
        rng = np.random.default_rng(5)
        base = rng.random((4, 20)) < 0.4
        txns = []
        for _ in range(120):
            pat = base[rng.integers(4)]
            row = np.where(rng.random(20) < 0.85, pat, rng.random(20) < 0.1)
            txns.append(np.nonzero(row)[0].tolist() or [0])
        oracle = sequential_apriori(txns, 0.3)
        from repro.compat import make_mesh
        mesh = make_mesh((4, 2), ("data", "model"))
        rt = MapReduceRuntime(mesh=mesh, cand_axis="model")
        res = mine(txns, n_items=20, min_sup=0.3, algorithm="optimized_vfpc",
                   runtime=rt)
        assert res.itemsets() == oracle
        print("2D_OK")
    """)
    assert "2D_OK" in out


def test_2d_candidate_decomposition_narrow_shards():
    """cand_axis wide enough that per-shard candidate counts are NOT a
    multiple of 32 (256-row bucket / 16 shards = 16): the fused keep mask
    must survive the shard concatenation (regression: per-shard bit-packing
    padded each shard to a word boundary and corrupted the global mask)."""
    out = run_py("""
        import jax, numpy as np
        from repro.core import mine, sequential_apriori
        from repro.core.mapreduce import MapReduceRuntime
        from repro.compat import make_mesh
        rng = np.random.default_rng(9)
        base = rng.random((4, 20)) < 0.4
        txns = []
        for _ in range(96):
            pat = base[rng.integers(4)]
            row = np.where(rng.random(20) < 0.85, pat, rng.random(20) < 0.1)
            txns.append(np.nonzero(row)[0].tolist() or [0])
        oracle = sequential_apriori(txns, 0.3)
        mesh = make_mesh((1, 16), ("data", "model"))
        rt = MapReduceRuntime(mesh=mesh, cand_axis="model", autotune=False)
        res = mine(txns, n_items=20, min_sup=0.3, algorithm="optimized_vfpc",
                   runtime=rt)
        assert res.itemsets() == oracle
        print("2D_NARROW_OK")
    """, n_devices=16)
    assert "2D_NARROW_OK" in out


def test_2d_mesh_parity_all_families():
    """The runtime-owned (data, cand) mesh at both (4,2) and (2,4) splits,
    across impl families including the matmul twins — every shape must be
    bit-identical to the sequential oracle (DESIGN.md §11)."""
    out = run_py("""
        import numpy as np
        from repro.core import mine, sequential_apriori
        from repro.core.mapreduce import MapReduceRuntime
        from repro.compat import make_mesh
        rng = np.random.default_rng(11)
        base = rng.random((4, 24)) < 0.4
        txns = []
        for _ in range(160):
            pat = base[rng.integers(4)]
            row = np.where(rng.random(24) < 0.85, pat, rng.random(24) < 0.1)
            txns.append(np.nonzero(row)[0].tolist() or [0])
        oracle = sequential_apriori(txns, 0.25)
        for split in [(4, 2), (2, 4)]:
            for impl in ["jnp", "matmul", "vertical", "vertical_matmul"]:
                mesh = make_mesh(split, ("data", "cand"))
                rt = MapReduceRuntime(mesh=mesh, impl=impl, cand_axis="cand")
                res = mine(txns, n_items=24, min_sup=0.25,
                           algorithm="optimized_etdpc", runtime=rt,
                           elastic=False)
                assert res.itemsets() == oracle, (split, impl)
        print("MESH2D_FAMILIES_OK")
    """)
    assert "MESH2D_FAMILIES_OK" in out


def test_repartition_mid_mine_parity():
    """Elastic repartitioning mid-mine: scripted choose_mesh walks the run
    through (8,1) → (2,4) → (4,2) splits and results stay bit-identical,
    with the re-layouts visible in MiningResult.repartitions."""
    out = run_py("""
        import numpy as np
        from repro.core import mine, sequential_apriori
        from repro.core.mapreduce import MapReduceRuntime
        from repro.costmodel import CostController
        from repro.costmodel.model import CostModel
        from repro.launch.mesh import make_mining_mesh
        rng = np.random.default_rng(12)
        base = rng.random((4, 24)) < 0.4
        txns = []
        for _ in range(200):
            pat = base[rng.integers(4)]
            row = np.where(rng.random(24) < 0.85, pat, rng.random(24) < 0.1)
            txns.append(np.nonzero(row)[0].tolist() or [0])
        oracle = sequential_apriori(txns, 0.25)
        rt = MapReduceRuntime(mesh=make_mining_mesh(8, 1), impl="jnp")
        ctl = CostController(model=CostModel(persist=False))
        script = iter([(2, 4), (4, 2)])
        ctl.choose_mesh = lambda *a, **k: next(script, None)
        res = mine(txns, n_items=24, min_sup=0.25,
                   algorithm="optimized_etdpc", runtime=rt,
                   controller=ctl, elastic=True)
        assert res.repartitions == 2, res.repartitions
        assert rt.mesh_split == (4, 2)
        assert res.itemsets() == oracle
        print("REPARTITION_OK")
    """)
    assert "REPARTITION_OK" in out


def test_retry_after_injected_failure():
    """A counting job that dies mid-phase (injected via count_hook) is
    recovered by rescatter + re-dispatch on the 2-D mesh, bit-identically."""
    out = run_py("""
        import numpy as np
        from repro.core import mine, sequential_apriori
        from repro.core.mapreduce import MapReduceRuntime
        from repro.launch.mesh import make_mining_mesh
        rng = np.random.default_rng(13)
        base = rng.random((4, 24)) < 0.4
        txns = []
        for _ in range(160):
            pat = base[rng.integers(4)]
            row = np.where(rng.random(24) < 0.85, pat, rng.random(24) < 0.1)
            txns.append(np.nonzero(row)[0].tolist() or [0])
        oracle = sequential_apriori(txns, 0.25)
        calls = {"n": 0}
        def fail_twice(event, k):
            if event == "count_dispatch":
                calls["n"] += 1
                if calls["n"] in (2, 3):
                    raise RuntimeError("injected shard failure")
        rt = MapReduceRuntime(mesh=make_mining_mesh(4, 2), impl="jnp",
                              cand_axis="cand")
        res = mine(txns, n_items=24, min_sup=0.25,
                   algorithm="optimized_etdpc", runtime=rt,
                   elastic=False, count_hook=fail_twice)
        assert res.retries == 2, res.retries
        assert res.itemsets() == oracle
        # beyond max_retries the failure propagates
        calls["n"] = 0
        def always_fail(event, k):
            if event == "count_dispatch":
                raise RuntimeError("dead shard")
        try:
            mine(txns, n_items=24, min_sup=0.25, runtime=rt,
                 elastic=False, count_hook=always_fail, max_retries=1)
            raise AssertionError("expected failure to propagate")
        except RuntimeError as e:
            assert "dead shard" in str(e)
        print("RETRY_OK")
    """)
    assert "RETRY_OK" in out


def test_balanced_shards_mining():
    """Width-balanced sharding (static straggler mitigation) keeps results exact."""
    out = run_py("""
        import numpy as np
        from repro.core import mine, sequential_apriori
        rng = np.random.default_rng(6)
        txns = [sorted(rng.choice(24, rng.integers(2, 14), replace=False).tolist())
                for _ in range(200)]
        oracle = sequential_apriori(txns, 0.2)
        res = mine(txns, n_items=24, min_sup=0.2, algorithm="vfpc",
                   balance_shards_by_width=True)
        assert res.itemsets() == oracle
        print("BALANCED_OK")
    """)
    assert "BALANCED_OK" in out


def test_decode_profile_parity():
    """The §Perf `decode` sharding profile (weights replicated over data,
    KV-seq on model) preserves decode semantics: prefill + decode-step logits
    match the unsharded run up to bf16 reduction-order noise."""
    out = run_py("""
        import jax, numpy as np
        import jax.numpy as jnp
        from repro.configs import get_config
        from repro.models import build_model
        from repro.models.model import ShardCtx
        from repro import sharding
        cfg = get_config("smollm-135m", smoke=True)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        B, S, steps = 4, 8, 3
        toks = np.random.default_rng(0).integers(1, cfg.vocab_size, (B, S)).astype(np.int32)

        forced = np.random.default_rng(1).integers(
            1, cfg.vocab_size, (steps, B)).astype(np.int32)

        def rollout(ctx):
            # teacher-forced so numeric tie-flips cannot compound
            batch = {"tokens": jnp.asarray(toks)}
            lgs = []
            lg, caches = model.prefill(params, batch, cache_len=S+steps, ctx=ctx)
            lgs.append(np.asarray(lg))
            for t in range(steps - 1):
                cur = jnp.asarray(forced[t])
                lg, caches = model.decode_step(params, caches, cur[:, None],
                                               jnp.full((B,), S+t, jnp.int32), ctx)
                lgs.append(np.asarray(lg))
            return np.stack(lgs)

        base = rollout(ShardCtx(None, None))
        from repro.compat import make_mesh
        mesh = make_mesh((2, 4), ("data", "model"))
        rules = sharding.make_rules("decode")
        sharded = rollout(ShardCtx(mesh, rules))
        err = np.abs(base - sharded)[:, :, :cfg.vocab_size].max()
        assert err < 0.05, err
        print("DECODE_PROFILE_OK", err)
    """)
    assert "DECODE_PROFILE_OK" in out


def test_speedup_harness_runs():
    """Mining wall time measured at 1 and 4 devices (speedup bench harness)."""
    for n in [1, 4]:
        out = run_py(f"""
            import time, numpy as np
            from repro.data import dataset_by_name
            from repro.core import mine
            txns, n_items = dataset_by_name("mushroom", scale=0.05)
            t0 = time.perf_counter()
            res = mine(txns, n_items=n_items, min_sup=0.4,
                       algorithm="optimized_vfpc")
            print("TIME", time.perf_counter() - t0, res.n_phases)
        """, n_devices=n)
        assert "TIME" in out


_COUNTED_MINE = """
    import jax, numpy as np
    from repro.core import mine
    from repro.core.mapreduce import MapReduceRuntime
    from repro.launch.mesh import make_mining_mesh
    from repro.obs.metrics import get_registry
    from repro.obs.trace import Tracer, use_tracer
    rng = np.random.default_rng(3)
    base = rng.random((4, 24)) < 0.4
    txns = [np.nonzero(np.where(rng.random(24) < 0.85, base[rng.integers(4)],
                                rng.random(24) < 0.1))[0].tolist() or [0]
            for _ in range(203)]
    placed = []
    put = jax.device_put

    def spy(x, *a, **k):
        placed.append(np.asarray(x).nbytes)
        return put(x, *a, **k)
    jax.device_put = spy

    def counted(n_data, n_cand, impl):
        rt = MapReduceRuntime(mesh=make_mining_mesh(n_data, n_cand),
                              impl=impl, autotune=False,
                              cand_axis="cand" if n_cand > 1 else None)
        placed.clear()
        tr = Tracer()
        x0 = get_registry().value("mine.exchange_bytes") or 0
        with use_tracer(tr):
            res = mine(txns, n_items=24, min_sup=0.3, algorithm="spc",
                       runtime=rt, elastic=False, pipeline=False,
                       balance_shards_by_width=False)
        x1 = get_registry().value("mine.exchange_bytes") or 0
        return rt, res, tr, list(placed), x1 - x0
"""


def test_exchange_and_device_copies_counted_on_a_4x1_mesh():
    """On four data shards every count job puts rows x 4 B into the psum on
    each of the four chips, and each payload is placed once per chip; on a
    2x2 candidate-sharded mesh a chip holds half the rows, and the
    scatter goes to both candidate shards."""
    out = run_py(_COUNTED_MINE + """
    for n_data, n_cand, impl in [(4, 1, "vertical"), (4, 1, "jnp"),
                                 (2, 2, "vertical")]:
        rt, res, tr, placed, registry = counted(n_data, n_cand, impl)
        jobs = [s for s in tr.spans if s.name == "mine.count"]
        assert len(jobs) == res.dispatches >= 2
        for s in jobs:
            rows = s.attrs["padded"]
            if n_cand > 1:
                rows += (-rows) % (32 * n_cand)
            assert s.attrs["exchange_bytes"] == rows // n_cand * 4 * 4, s.attrs
        total = sum(s.attrs["exchange_bytes"] for s in jobs)
        assert res.exchange_bytes == rt.stats.exchange_bytes == total > 0
        assert registry == total
        # the scatter, then one payload per job: the host arrays handed to
        # device_put, times the chips that hold a copy of each
        assert len(placed) == 1 + res.dispatches
        assert res.bytes_to_device == rt.stats.bytes_to_device == (
            placed[0] * n_cand + sum(placed[1:]) * n_data)
    print("EXCHANGE_OK")
    """, n_devices=4)
    assert "EXCHANGE_OK" in out


def test_one_device_exchanges_nothing_and_counts_each_array_once():
    out = run_py(_COUNTED_MINE + """
    for impl in ["vertical", "jnp"]:
        rt, res, tr, placed, registry = counted(1, 1, impl)
        assert res.exchange_bytes == rt.stats.exchange_bytes == 0
        assert registry == 0
        assert all(s.attrs["exchange_bytes"] == 0
                   for s in tr.spans if s.name == "mine.count")
        assert res.bytes_to_device == sum(placed) > 0
    print("ONE_DEVICE_OK")
    """, n_devices=1)
    assert "ONE_DEVICE_OK" in out


def test_scatter_span_carries_shards_and_pack_seconds():
    out = run_py(_COUNTED_MINE + """
    for impl in ["vertical", "jnp"]:
        rt, res, tr, placed, registry = counted(4, 1, impl)
        (s,) = [s for s in tr.spans if s.name == "mine.scatter"]
        assert s.attrs["shards"] == 4
        assert 0.0 <= s.attrs["pack_seconds"] <= s.duration
        assert (s.attrs["pack_seconds"] > 0) == impl.startswith("vertical")
        assert rt.stats.pack_seconds == s.attrs["pack_seconds"]
        assert rt.stats.pack_seconds <= rt.stats.scatter_seconds
    print("SCATTER_OK")
    """, n_devices=4)
    assert "SCATTER_OK" in out
