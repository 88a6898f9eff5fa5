"""Distribution-layer tests. Multi-device cases run in SUBPROCESSES with
--xla_force_host_platform_device_count (the main test process must keep the
single real device; see conftest)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

pytestmark = [pytest.mark.distributed, pytest.mark.slow]

SRC = os.path.join(os.path.dirname(__file__), "..", "src")
TESTS = os.path.dirname(__file__)


def run_sub(code: str, devices: int = 4) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.pathsep.join([SRC, TESTS])
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, env=env, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_fit_spec_divisibility():
    from repro.distributed.sharding import fit_spec
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1),
                             ("data", "model"))
    # non-dividing dims fall back to None / a dividing subgroup
    assert fit_spec(mesh, P("data"), (13,)) == P("data")  # 13 % 1 == 0 here
    mesh2 = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1,), ("x",))
    assert fit_spec(mesh2, P("x", None), (7, 3)) == P("x", None)


def test_fit_spec_logic_pure():
    """Pure spec-fitting logic with a fake mesh shape."""
    class FakeMesh:
        shape = {"pod": 2, "data": 16, "model": 16}
        axis_names = ("pod", "data", "model")
    from repro.distributed.sharding import fit_spec
    m = FakeMesh()
    assert fit_spec(m, P(("pod", "data"), "model"), (64, 64)) == P(("pod", "data"), "model")
    # 49155 divides by nothing here -> None; 1024 / fsdp(32) ok
    got = fit_spec(m, P("model", ("pod", "data")), (49155, 1024))
    assert got == P(None, ("pod", "data"))
    # 1e6 % 256 != 0 but % 16 == 0 -> shrinks to a dividing subgroup
    got = fit_spec(m, P(("data", "model"),), (1_000_000,))
    assert got in (P("data"), P(("data",),))


def test_sharded_kernels_and_vp_loss_subprocess():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.kernels.arena_scan.ref import arena_scan_ref
        from repro.kernels.arena_scan.sharded import make_sharded_arena_scan
        from repro.kernels.decode_attention.ops import decode_attention_sharded
        from repro.kernels.decode_attention.ref import decode_attention_ref
        from repro.launch.mesh import make_mesh
        from repro.models.transformer import TransformerConfig, init, loss_fn, make_vp_loss_fn

        rng = np.random.default_rng(0)
        mesh = make_mesh((2, 2), ("data", "model"))

        # sharded arena scan == global oracle
        N, D, kk = 2048, 64, 7
        q = jnp.asarray(rng.standard_normal((3, D), dtype=np.float32))
        emb = jnp.asarray(rng.standard_normal((N, D), dtype=np.float32))
        meta = jnp.stack([jnp.asarray(rng.integers(-1, 5, N, dtype=np.int32)),
                          jnp.asarray(rng.integers(0, 99, N, dtype=np.int32)),
                          jnp.asarray(rng.integers(0, 4, N, dtype=np.int32)),
                          jnp.asarray(rng.integers(1, 8, N, dtype=np.int32))], 0)
        pred = jnp.array([1, 20, 0b1010, 0b11], jnp.int32)
        store = {"emb": emb, "tenant": meta[0], "updated_at": meta[1],
                 "category": meta[2], "acl": meta[3].astype(jnp.uint32),
                 "doc_id": jnp.arange(N, dtype=jnp.int32),
                 "version": jnp.zeros(N, jnp.int32),
                 "commit_ts": jnp.int32(0), "n_live": jnp.int32(N)}
        sharded = make_sharded_arena_scan(mesh, ("data", "model"), N, kk)
        s1, i1, _ = sharded(store, q, pred)
        s2, i2 = arena_scan_ref(q, emb, meta, jnp.zeros(3, jnp.int32),
                                pred[None, :], kk)
        np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-5, atol=1e-5)

        # sharded flash-decode == oracle across shard-crossing lengths
        B, S, KV, G, hd = 2, 1024, 2, 4, 64
        qd = jnp.asarray(rng.standard_normal((B, KV*G, hd), dtype=np.float32))
        kc = jnp.asarray(rng.standard_normal((B, S, KV, hd), dtype=np.float32))
        vc = jnp.asarray(rng.standard_normal((B, S, KV, hd), dtype=np.float32))
        lengths = jnp.asarray([300, 900], jnp.int32)
        outd = decode_attention_sharded(mesh, "model", qd, kc, vc, lengths,
                                        n_kv=KV, blk_s=128)
        refd = decode_attention_ref(qd.reshape(B, KV, G, hd), kc, vc,
                                    lengths).reshape(B, KV*G, hd)
        np.testing.assert_allclose(np.asarray(outd), np.asarray(refd),
                                   rtol=2e-5, atol=2e-5)

        # vocab-parallel CE == plain loss (values + grads)
        cfg = TransformerConfig(name="t", n_layers=2, d_model=32, n_heads=4,
                                n_kv_heads=2, d_ff=64, vocab_size=128,
                                dtype="float32")
        params = init(jax.random.PRNGKey(0), cfg)
        toks = jnp.asarray(rng.integers(0, 128, (4, 16), dtype=np.int32))
        batch = {"tokens": toks, "labels": toks}
        vp = make_vp_loss_fn(cfg, mesh)
        np.testing.assert_allclose(float(loss_fn(params, cfg, batch)),
                                   float(vp(params, batch)), rtol=1e-5)
        g1 = jax.grad(loss_fn)(params, cfg, batch)
        g2 = jax.grad(vp)(params, batch)
        for a, b in zip(jax.tree_util.tree_leaves(g1), jax.tree_util.tree_leaves(g2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)
        print("SUBPROCESS_OK")
    """)
    assert "SUBPROCESS_OK" in out


def test_sharded_arena_scan_subprocess():
    """The sharded engine's device-level contracts on an 8-way CPU mesh:
    bit-identity with the dense oracle, the O(S*B*k) collective-payload
    bound asserted from compiled HLO, the per-shard rows audit, and
    placement INVARIANCE under constructed score ties (shuffling which
    shard holds which rows cannot change the returned (score, doc_id)
    lists bit-wise)."""
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.query import unified_query_ref
        from repro.kernels.arena_scan.sharded import (
            make_sharded_arena_scan, sharded_collective_bytes)
        from repro.launch.mesh import make_mesh

        rng = np.random.default_rng(0)
        N, D, k, S = 4096, 32, 10, 8
        mesh = make_mesh((S,), ("data",))

        def store_of(emb, tenant, cat, ts, doc_id):
            n = emb.shape[0]
            return {"emb": jnp.asarray(emb), "tenant": jnp.asarray(tenant),
                    "category": jnp.asarray(cat, jnp.int32),
                    "updated_at": jnp.asarray(ts, jnp.int32),
                    "acl": jnp.asarray(np.full(n, 3), jnp.uint32),
                    "doc_id": jnp.asarray(doc_id, jnp.int32),
                    "version": jnp.zeros(n, jnp.int32),
                    "commit_ts": jnp.int32(1), "n_live": jnp.int32(n)}

        emb = rng.standard_normal((N, D), dtype=np.float32)
        tenant = rng.integers(0, 16, N).astype(np.int32)
        cat = rng.integers(0, 4, N).astype(np.int32)
        ts = rng.integers(1, 99, N).astype(np.int32)
        store = store_of(emb, tenant, cat, ts, np.arange(N))
        q = rng.standard_normal((3, D), dtype=np.float32)
        pred = jnp.array([-2, 10, -1, -1], jnp.int32)

        fn = make_sharded_arena_scan(mesh, ("data",), N, k)
        s, sl, rows = fn(store, jnp.asarray(q), pred)
        s0, i0 = unified_query_ref(store, jnp.asarray(q), pred, k)
        assert np.array_equal(np.asarray(s), np.asarray(s0))
        assert np.array_equal(np.asarray(sl), np.asarray(i0))
        assert np.asarray(rows).tolist() == [N // S] * S
        print("ORACLE_OK")

        # collective payload: 3 gathered (B_pad, k) lists per shard -> the
        # issue's O(S*B*k) bound, and a vanishing fraction of arena bytes
        cbytes = sharded_collective_bytes(fn, store, jnp.asarray(q), pred)
        B_pad = 8                         # query block lane-padded to 8
        assert 0 < cbytes <= 2 * S * B_pad * k * 8, cbytes
        # (the <0.1%-of-arena-bytes fraction is asserted at bench scale,
        # N=1M, by tools/check_bench_regression.py --sharded-only)
        print("PAYLOAD_OK", cbytes)

        # placement invariance under constructed ties: 64 rows share ONE
        # embedding (exact f32 score ties); shuffle which shard holds which
        # rows and the merged (score, doc_id) lists must not move
        emb_t = emb.copy(); emb_t[:64] = emb_t[0]
        perm = rng.permutation(N)
        docs = np.arange(N)
        fn2 = make_sharded_arena_scan(mesh, ("data",), N, k)
        outs = []
        for order in (docs, perm):
            st2 = store_of(emb_t[order], tenant[order], cat[order],
                           ts[order], docs[order])
            s2, sl2, _ = fn2(st2, jnp.asarray(q), pred)
            sl2 = np.asarray(sl2)
            ids = np.where(sl2 >= 0, docs[order][sl2], -1)
            outs.append((np.asarray(s2), ids))
        assert np.array_equal(outs[0][0], outs[1][0])
        assert np.array_equal(outs[0][1], outs[1][1])
        print("PLACEMENT_INVARIANT_OK")
    """, devices=8)
    assert "ORACLE_OK" in out and "PAYLOAD_OK" in out
    assert "PLACEMENT_INVARIANT_OK" in out


def test_sharded_ragdb_affine_subprocess():
    """End-to-end mesh-built RagDB at S=8 with tenant-affine placement: the
    property-test sweep from test_property_isolation runs here with REAL
    multi-shard structural skips (owning shard only, poisoned foreign shard
    never surfaces, bits match the oracle)."""
    out = run_sub("""
        from test_property_isolation import (_args_from_seed,
                                             _check_sharded_affine_isolation)
        for seed in range(4):
            _check_sharded_affine_isolation(_args_from_seed(seed))
        print("AFFINE_PROPERTY_OK")
    """, devices=8)
    assert "AFFINE_PROPERTY_OK" in out


def test_mini_dryrun_subprocess():
    """build_cell machinery on a small mesh: one cheap cell per family."""
    out = run_sub("""
        import jax
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import build_cell
        mesh = make_mesh((2, 2), ("data", "model"))
        for arch, shape in [("qwen1.5-0.5b", "decode_32k"), ("fm", "serve_p99"),
                            ("gcn-cora", "molecule"), ("rag-unified", "ingest")]:
            cell = build_cell(arch, shape, mesh)
            c = jax.jit(cell.fn, in_shardings=cell.in_shardings,
                        out_shardings=cell.out_shardings).lower(*cell.args).compile()
            assert c.memory_analysis() is not None
            print("CELL_OK", arch, shape)
    """, devices=4)
    assert out.count("CELL_OK") == 4


def test_compression_psum_subprocess():
    out = run_sub("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.distributed.compression import psum_bf16, psum_int8
        from repro.launch.mesh import make_mesh
        mesh = make_mesh((4,), ("d",))
        x = jnp.asarray(np.random.default_rng(0).standard_normal((4, 256), np.float32))
        want = np.asarray(x).sum(0)
        for fn, tol in [(psum_bf16, 2e-2), (psum_int8, 4e-2)]:
            f = jax.shard_map(lambda v: fn(v, "d"), mesh=mesh,
                              in_specs=P("d"), out_specs=P("d"),
                              check_vma=False)
            got = np.asarray(f(x))[0]
            rel = np.abs(got - want).max() / np.abs(want).max()
            assert rel < tol, (fn.__name__, rel)
        print("PSUM_OK")
    """, devices=4)
    assert "PSUM_OK" in out
