"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed on CPU-only machines: a topology can be
described and programs compiled for it (nothing runs). These tests compile
the arena-scan programs at the production width (D=768, k=10) through the
wrappers the executor calls, and check that each compiled program holds
its Pallas kernel (`tpu_custom_call`) — so a kernel Mosaic refuses (an
unlowerable primitive, an unaligned DMA slice, a VMEM overflow) fails here
and not on the chip. The topology is described inside a module-scoped
fixture, never at import: only the worker that runs this file loads the
TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.api.planner import PlannerConfig
from repro.core.store import ShardPlacement, StoreConfig, _empty_lanes
from repro.kernels.arena_scan.ops import default_blk_b
from repro.kernels.arena_scan.stages import ScanSpec
from repro.kernels.arena_scan.sharded import make_sharded_arena_scan
from repro.kernels.grouped_topk.ops import grouped_topk
from repro.kernels.hybrid_score.ops import hybrid_score
from repro.kernels.ivf_probe.ops import ivf_probe

pytestmark = pytest.mark.kernels

D, K, B = 768, 10, 8
N = 8 * 2048              # a multiple of the resident tile and of a page
T, QT, V = 16, 16, 2048   # LexicalConfig lanes, max query terms, vocab


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from the cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _arena(sharding):
    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
    return sds, dict(q=sds((B, D), jnp.float32), emb=sds((N, D), jnp.float32),
                     tenant=sds((N,), jnp.int32), ts=sds((N,), jnp.int32),
                     cat=sds((N,), jnp.int32), acl=sds((N,), jnp.uint32))


def _assert_kernel(fn, *args):
    txt = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("groups", [1, 8])
def test_dense_resident_compiles(one_chip, groups):
    sds, a = _arena(one_chip)
    _assert_kernel(
        lambda q, e, t, ts, c, acl, g, p: grouped_topk(
            q, e, t, ts, c, acl, g, p, K, use_kernel=True, interpret=False),
        a["q"], a["emb"], a["tenant"], a["ts"], a["cat"], a["acl"],
        sds((B,), jnp.int32), sds((groups, 4), jnp.int32))


def test_dense_paged_compiles_at_planner_page(one_chip):
    sds, a = _arena(one_chip)
    page = PlannerConfig().page_rows
    _assert_kernel(
        lambda q, e, t, ts, c, acl, g, p: grouped_topk(
            q, e, t, ts, c, acl, g, p, K, use_kernel=True, interpret=False,
            page_rows=page),
        a["q"], a["emb"], a["tenant"], a["ts"], a["cat"], a["acl"],
        sds((B,), jnp.int32), sds((1, 4), jnp.int32))


@pytest.mark.parametrize("rows,paged", [(16, False), (128, False),
                                        (16, True)])
def test_dense_query_block_compiles(one_chip, rows, paged):
    """The dense kernel at its default query-row block (the launch's whole
    batch, up to the MXU's 128 rows), resident and at the planner's page:
    Mosaic takes the wider block within the scoped VMEM."""
    assert default_blk_b(rows, ScanSpec()) == rows
    sds, a = _arena(one_chip)
    page = PlannerConfig().page_rows if paged else None
    _assert_kernel(
        lambda q, e, t, ts, c, acl, g, p: grouped_topk(
            q, e, t, ts, c, acl, g, p, K, use_kernel=True, interpret=False,
            page_rows=page),
        sds((rows, D), jnp.float32), a["emb"], a["tenant"], a["ts"],
        a["cat"], a["acl"], sds((rows,), jnp.int32), sds((4, 4), jnp.int32))


def test_ivf_slot_lane_compiles(one_chip):
    sds, a = _arena(one_chip)
    _assert_kernel(
        lambda q, e, t, ts, c, acl, m, o, cl, p: ivf_probe(
            q, e, t, ts, c, acl, m, o, cl, p, K, use_kernel=True,
            interpret=False),
        a["q"], a["emb"], a["tenant"], a["ts"], a["cat"], a["acl"],
        sds((256, 64), jnp.int32), sds((512,), jnp.int32),
        sds((16,), jnp.int32), sds((4,), jnp.int32))


#: MS MARCO widths: 64 postings lanes of BERT-base's 30,522 WordPiece
#: ids.
T_MSMARCO, V_MSMARCO = 64, 30522


@pytest.mark.parametrize("mode,lanes,vocab,page", [
    pytest.param("wsum", T, V, None, id="wsum"),          # fused list
    pytest.param("rrf", T, V, None, id="rrf"),            # both lists
    pytest.param("wsum", T_MSMARCO, V_MSMARCO, None, id="wsum-T64"),
    pytest.param("rrf", T_MSMARCO, V_MSMARCO, None, id="rrf-T64"),
    pytest.param("wsum", T_MSMARCO, V_MSMARCO, 2048, id="wsum-T64-paged"),
    pytest.param("rrf", T_MSMARCO, V_MSMARCO, 2048, id="rrf-T64-paged"),
])
def test_hybrid_compiles(one_chip, mode, lanes, vocab, page):
    """The hybrid kernel at QT = 16 query terms, at the program's default
    16 lanes and at MS MARCO's 64 lanes, resident (512-row
    tiles) and at the planner's 2048-row page: the unrolled T x QT lexical
    loop fits the scoped VMEM."""
    assert page in (None, PlannerConfig().page_rows)
    sds, a = _arena(one_chip)
    _assert_kernel(
        lambda q, e, t, ts, c, acl, tm, ln, idf, g, p, qt: hybrid_score(
            q, e, t, ts, c, acl, tm, ln, idf, g, p, qt, K, mode=mode,
            use_kernel=True, interpret=False, page_rows=page),
        a["q"], a["emb"], a["tenant"], a["ts"], a["cat"], a["acl"],
        sds((N, lanes), jnp.int32), sds((N, lanes), jnp.float32),
        sds((vocab,), jnp.float32), sds((B,), jnp.int32),
        sds((4, 4), jnp.int32), sds((B, QT), jnp.int32))


def test_sharded_engine_compiles_on_four_chips(topo):
    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    cfg = StoreConfig(capacity=4 * N, dim=D)
    placement = ShardPlacement(n_shards=4, capacity=cfg.capacity,
                               kind="tenant", mesh=mesh, axes=("data",))
    shardings = placement.shardings()
    store = {k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=shardings[k])
             for k, v in jax.eval_shape(lambda: _empty_lanes(cfg)).items()}
    rep = NamedSharding(mesh, P())
    fn = make_sharded_arena_scan(mesh, ("data",), cfg.capacity, K,
                                 placement_kind="tenant")
    compiled = fn.lower(store,
                        jax.ShapeDtypeStruct((B, D), jnp.float32, sharding=rep),
                        jax.ShapeDtypeStruct((4,), jnp.int32, sharding=rep)
                        ).compile()
    txt = compiled.as_text()
    assert "all-gather" in txt
    # each chip holds a quarter of the arena, never the whole of it
    arena_bytes = cfg.capacity * D * 4
    assert compiled.memory_analysis().argument_size_in_bytes < arena_bytes / 2
