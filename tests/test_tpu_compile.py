"""Compile the main path for a described TPU v5e, without a chip.

The TPU compiler is installed with jaxlib: it compiles for a described
``v5e:2x2`` topology from shapes alone and refuses what the chip would
refuse (unlowerable kernels, kernels over their memory budget, programs
over the chip's HBM).  Nothing runs here, so these tests say nothing about
results or times.  The topology is described inside a fixture (never at
import) so every pytest worker collects the same tests.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

HBM_BYTES = 16 * 1024**3  # one TPU v5e chip
# the hepph stand-in at its published n (paper_dataset("hepph", 1.0))
HEPPH_N, HEPPH_K, HEPPH_M = 34_546, 34_541, 149_381


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "no compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_fused_serve_fits_one_chip_at_hepph(one_chip):
    from repro.core.multisource import _fused_serve
    from repro.core.params import make_params
    from repro.graph.structs import EllGraph, Graph

    n, k, m, q = HEPPH_N, HEPPH_K, HEPPH_M, 16
    cap = m + 4096
    i32 = jnp.int32
    s = lambda shape, dt=i32: _sds(shape, dt, one_chip)  # noqa: E731
    g = Graph(src=s((cap,)), dst=s((cap,)), in_deg=s((n,)), out_deg=s((n,)),
              num_edges=s(()), n=n, capacity=cap, version=s(()),
              overflow=s((), jnp.bool_))
    eg = EllGraph(in_nbrs=s((n, k)), in_deg=s((n,)), n=n, k_max=k,
                  version=s(()), overflow=s((), jnp.bool_))
    keys = s((q,), jax.random.key(0).dtype)
    p = make_params(n, c=0.6, eps_a=0.1, delta=0.01)
    compiled = _fused_serve.lower(
        keys, g, eg, s((q,)), s((q, n), jnp.float32), s(()),
        n_r=p.n_r, lanes_q=16, max_len=p.max_len, sqrt_c=p.sqrt_c,
        eps_p=p.eps_p, eps_t=p.eps_t, truncation_shift=False, top_k=50,
    ).compile()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 4 * n * k <= used < HBM_BYTES


def test_sharded_serve_step_compiles_on_four_chips(topo):
    from repro.core.epoch import ShardEpochGraph, make_sharded_serve_step

    mesh = jax.sharding.Mesh(
        np.asarray(topo.devices).reshape(1, 4), ("data", "model"),
        axis_types=(jax.sharding.AxisType.Auto,) * 2,
    )
    n, shards, e, k_max, q = 4000, 4, 8192, 64, 16
    sh = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    i32 = jnp.int32
    st = ShardEpochGraph(
        src_sh=_sds((shards, e), i32, sh(P("model", None))),
        dst_sh=_sds((shards, e), i32, sh(P("model", None))),
        counts=_sds((shards,), i32, sh(P("model"))),
        in_nbrs=_sds((n, k_max), i32, sh(P("model", None))),
        in_deg=_sds((n,), i32, sh(P())),
        n=n, n_pad=n, rows=n // shards, shards=shards, capacity=e,
        k_max=k_max,
    )
    step = make_sharded_serve_step(
        st, mesh, q=q, n_r=512, lanes_q=16, top_k=10, max_len=12,
        sqrt_c=0.775, eps_p=0.005, eps_t=0.05, truncation_shift=False,
    )
    compiled = step.lower(
        st, _sds((q,), i32, sh(P())),
        _sds((q,), jax.random.key(0).dtype, sh(P())),
    ).compile()
    hlo = compiled.as_text()
    assert "all-gather" in hlo


# the benchmark's realized graphs (bench/configs): cit-hepph, and
# wiki-vote at its COO capacity (m + 1,024 spare slots)
BENCH_GRAPHS = {"hepph": (34_546, 421_578), "wiki": (7_115, 104_713)}
# their ELL widths (bench/configs realized max_in_degree / ell_width)
BENCH_ELL_K = {"hepph": 1_139, "wiki": 2_669}


def _coo_shapes(n, cap, sharding):
    from repro.graph.structs import Graph

    s = lambda shape, dt=jnp.int32: _sds(shape, dt, sharding)  # noqa: E731
    return Graph(src=s((cap,)), dst=s((cap,)), in_deg=s((n,)),
                 out_deg=s((n,)), num_edges=s(()), n=n, capacity=cap,
                 version=s(()), overflow=s((), jnp.bool_))


@pytest.mark.parametrize("cell", sorted(BENCH_GRAPHS))
def test_csr_level_lowers_at_bench_shape(one_chip, cell):
    """The CSR level compiles for the chip at the benchmark's realized
    shapes (W = 256), over the view ``csr_push_view`` gives the graph."""
    from repro.kernels.lane_probe.lane_probe import CSR_CHUNK
    from repro.kernels.lane_probe.ops import (
        csr_layout, csr_level_fits, lane_probe_csr_level,
    )

    n, cap = BENCH_GRAPHS[cell]
    W = 256
    assert csr_level_fits(n, W)
    rows, _ = csr_layout(n)
    ids = -(-cap // CSR_CHUNK) * CSR_CHUNK
    i32, f32 = jnp.int32, jnp.float32
    view = (_sds((rows + 1,), i32, one_chip), _sds((ids,), i32, one_chip),
            _sds((rows, 1), f32, one_chip))
    compiled = jax.jit(
        lambda view, *a: lane_probe_csr_level(view, *a, prune=True,
                                              interpret=False)
    ).lower(
        view, _sds((rows, W), f32, one_chip), _sds((rows, W), f32, one_chip),
        _sds((W,), jnp.bool_, one_chip),
        *(_sds((W,), i32, one_chip),) * 2, _sds((W,), f32, one_chip),
    ).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("cell", sorted(BENCH_GRAPHS))
def test_csr_fused_serve_fits_one_chip(one_chip, monkeypatch, cell):
    """The fused serve step on the CSR path, as a TPU runs it, compiles
    for the chip at the benchmark's realized shapes (16 queries, W = 256,
    the Thm-1 walk budget, top-50) and fits one chip's HBM; the level is
    the native kernel."""
    from repro.core.multisource import _fused_serve, push_path
    from repro.core.params import make_params
    from repro.graph.structs import EllGraph

    n, cap = BENCH_GRAPHS[cell]
    k, q = BENCH_ELL_K[cell], 16
    s = lambda shape, dt=jnp.int32: _sds(shape, dt, one_chip)  # noqa: E731
    g = _coo_shapes(n, cap, one_chip)
    eg = EllGraph(in_nbrs=s((n, k)), in_deg=s((n,)), n=n, k_max=k,
                  version=s(()), overflow=s((), jnp.bool_))
    p = make_params(n, c=0.6, eps_a=0.1, delta=0.01)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert push_path(g, q * 16) == "csr_kernel"
    jax.clear_caches()  # a traced step keeps the path it took
    try:
        compiled = _fused_serve.lower(
            s((q,), jax.random.key(0).dtype), g, eg, s((q,)),
            s((q, n), jnp.float32), s(()),
            n_r=p.n_r, lanes_q=16, max_len=p.max_len, sqrt_c=p.sqrt_c,
            eps_p=p.eps_p, eps_t=p.eps_t, truncation_shift=False, top_k=50,
        ).compile()
    finally:
        jax.clear_caches()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert 4 * n * k <= used < HBM_BYTES


def test_push_path_follows_the_frontier(monkeypatch):
    """On a TPU the default path takes the CSR kernel where the fp32
    frontier fits VMEM (both benchmark graphs) and keeps the XLA COO level
    where it does not (LiveJournal's 4.8M rows); off the TPU the XLA COO
    level."""
    from repro.core.multisource import push_path

    cpu = jax.sharding.SingleDeviceSharding(jax.devices("cpu")[0])
    lj = _coo_shapes(4_847_571, 68_993_773, cpu)
    hepph = _coo_shapes(*BENCH_GRAPHS["hepph"], cpu)
    assert push_path(hepph, 256) == "coo_xla"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert push_path(hepph, 256) == "csr_kernel"
    wiki = _coo_shapes(*BENCH_GRAPHS["wiki"], cpu)
    assert push_path(wiki, 256) == "csr_kernel"
    assert push_path(lj, 256) == "coo_xla"
