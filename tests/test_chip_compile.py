"""Every device kernel of the main path compiles for one TPU v5e chip at
the widths chip_smoke.py runs (on-chip-measurement guide, section 2):
the chip's compiler refuses here, at no chip time, what interpret mode
and the CPU backend accept — unaligned slices, VMEM overruns, programs
that do not fit.

Nothing runs: a compile that passes is not a chip run. The topology is
described inside a module fixture (never at import, in conftest, in a
skipif or a parametrize) so every xdist worker collects the same tests
and only the worker given this file loads the TPU compiler.
"""
import os

import numpy as np
import pytest

#: the smoke's metro graph (synth.build_grid_city(150, 150))
N_NODES, N_EDGES = 22_500, 89_400
K = 8
#: decode shapes: a full bucket-64 chunk, a bucket-256 chunk and the
#: batch pipeline's longest, a pipelined 128-row chunk at T 1024
DECODE_SHAPES = [(512, 64), (256, 256), (128, 1024)]
#: sources per relaxation block on the metro graph (route_device
#: _block_sources) and the rows of a full decode chunk
RELAX_BLOCK, ROUTE_B = 256, 128


def _decode_cases():
    """(backend, B, T) — pallas only where its VMEM estimate fits the
    budget; ops.decode_backend serves larger buckets with assoc."""
    from reporter_tpu.ops.pallas_viterbi import (VMEM_BUDGET_BYTES,
                                                 vmem_bytes_estimate)
    return [(b, B, T) for b in ("scan", "assoc", "pallas")
            for B, T in DECODE_SHAPES
            if b != "pallas"
            or vmem_bytes_estimate(T, K) <= VMEM_BUDGET_BYTES]


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """The sharding of one described v5e chip. The persistent cache is
    off around these compiles: an entry for a chip that is not attached
    cannot be read back here."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    import jax
    return jax.ShapeDtypeStruct(shape, np.dtype(dtype), sharding=sharding)


def _decode_args(B, T, sharding, data_sharding=None):
    """The production decode inputs: f16 wire tensors in the native
    layout (route/gc carry T time rows), bool validity, i32 cases."""
    d = data_sharding or sharding
    return (_spec((B, T, K), np.float16, d), _spec((B, T, K), bool, d),
            _spec((B, T, K, K), np.float16, d), _spec((B, T), np.float16, d),
            _spec((B, T), np.int32, d), _spec((), np.float32, sharding),
            _spec((), np.float32, sharding))


def _compile(fn, *args, **statics):
    import functools

    import jax
    compiled = jax.jit(functools.partial(fn, **statics)).lower(
        *args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


def _decode_kernel(name):
    if name == "scan":
        from reporter_tpu.matcher.hmm import viterbi_decode_batch
        return viterbi_decode_batch.__wrapped__, {}
    if name == "assoc":
        from reporter_tpu.ops.assoc_viterbi import viterbi_assoc_batch
        return viterbi_assoc_batch.__wrapped__, {}
    from reporter_tpu.ops.pallas_viterbi import viterbi_pallas_batch
    return viterbi_pallas_batch.__wrapped__, {"interpret": False}


@pytest.mark.parametrize("backend,B,T", _decode_cases())
def test_decode_compiles(one_chip, backend, B, T):
    fn, statics = _decode_kernel(backend)
    compiled = _compile(fn, *_decode_args(B, T, one_chip), **statics)
    if backend == "pallas":
        assert "tpu_custom_call" in compiled.as_text()


def test_incremental_step_compiles(one_chip):
    from reporter_tpu.matcher.incremental import ROUNDS_MAX
    from reporter_tpu.ops.incremental import incremental_step_batch
    R, B = ROUNDS_MAX, 512
    args = (_spec((R, B, K), np.float32, one_chip),
            _spec((R, B, K), bool, one_chip),
            _spec((R, B, K, K), np.float32, one_chip),
            _spec((R, B), np.float32, one_chip),
            _spec((R, B), np.int32, one_chip),
            _spec((R, B), bool, one_chip),
            _spec((B, K), np.float32, one_chip),
            _spec((), np.float32, one_chip), _spec((), np.float32, one_chip))
    _compile(incremental_step_batch.__wrapped__, *args)


def _edge_cols(sharding):
    return (_spec((N_EDGES,), np.int32, sharding),
            _spec((N_EDGES,), np.int32, sharding),
            _spec((N_EDGES,), np.float32, sharding),
            _spec((N_EDGES,), np.float32, sharding))


def test_relax_block_compiles(one_chip):
    from reporter_tpu.ops.route_relax import relax_csr
    args = _edge_cols(one_chip) + (
        _spec((RELAX_BLOCK,), np.int32, one_chip),
        _spec((), np.float32, one_chip))
    _compile(relax_csr.__wrapped__, *args, n_nodes=N_NODES,
             max_iters=N_NODES)


@pytest.mark.parametrize("T", [64, 256])
def test_pair_costs_packed_compiles(one_chip, T):
    from reporter_tpu.ops.route_relax import pair_costs_packed
    B, S = ROUTE_B, RELAX_BLOCK
    btk, bt1 = B * T * K, B * (T - 1)
    args = (_spec((btk + B + N_NODES,), np.int32, one_chip),
            _spec((btk + 2 * bt1 + 2,), np.float32, one_chip),
            _spec((S, N_NODES), np.float32, one_chip),
            _spec((S, N_NODES), np.float32, one_chip),
            _spec((), np.int32, one_chip),
            _spec((B, T - 1, K, K), np.float32, one_chip)) \
        + _edge_cols(one_chip) \
        + (_spec((N_EDGES,), np.float32, one_chip),
           _spec((N_EDGES,), np.float32, one_chip))
    _compile(pair_costs_packed.__wrapped__, *args, B=B, T=T, K=K,
             N=N_NODES)


def test_data_sharded_decode_compiles_on_four_chips(topo, one_chip):
    """match_many's decode on the 4-chip ("data",) mesh: assoc (the
    accelerator default), batch sharded, output left on 4 devices."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from reporter_tpu.ops.assoc_viterbi import viterbi_assoc_batch
    from reporter_tpu.parallel.mesh import make_data_mesh
    from reporter_tpu.parallel.sharded import data_decode_jit

    mesh = make_data_mesh(topo.devices[:4])
    B, T = 2048, 64
    args = _decode_args(B, T, NamedSharding(mesh, P()),
                        NamedSharding(mesh, P("data")))
    compiled = data_decode_jit(
        mesh, viterbi_assoc_batch.__wrapped__).lower(*args).compile()
    paths, _scores = compiled.output_shardings
    assert len(paths.device_set) == 4
    per_device = compiled.memory_analysis()
    assert per_device is not None
