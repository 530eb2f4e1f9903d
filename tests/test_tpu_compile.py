"""The device path compiled for a described TPU v5e chip.

Interpret mode (every other Pallas test) checks neither VMEM limits nor
tile alignment; the chip's compiler does.  These tests compile the Pallas
kernels at production widths, and the whole corpus scorer of the Sesam
stresstest schema at production geometry, with the TPU compiler that is
installed here, for a chip that is described and not attached.  Nothing
runs, so they say nothing about results or times.

The topology is described inside a fixture, never at import time: only
one process at a time may load the TPU library, and every xdist worker
imports this file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from sesam_duke_microservice_tpu.core import comparators as C
from sesam_duke_microservice_tpu.core.config import DukeSchema
from sesam_duke_microservice_tpu.core.records import (
    ID_PROPERTY_NAME,
    Property,
    Record,
)
from sesam_duke_microservice_tpu.ops import features as F
from sesam_duke_microservice_tpu.ops import pallas_kernels as pk
from sesam_duke_microservice_tpu.ops import scoring as S

# production geometry (engine.device_matcher / ops.features defaults;
# tests/conftest.py shrinks them for the CPU backend)
CHUNK = 8192
BUCKET = 4096
TOP_K = 64
CHARS = 32
GRAMS = 64
CAPACITY = 131072  # 100k records, grown in chunk-multiple doublings
HBM = 16 * (1 << 30)


@pytest.fixture(scope="module")
def topo():
    """A described v5e:2x2, with the persistent compilation cache off (a
    compile for a described chip is written to it but cannot be read
    back without one)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        # the TPU compiler otherwise logs under /tmp
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler installed here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield topo
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh4(topo):
    from sesam_duke_microservice_tpu.parallel.sharded import corpus_mesh

    return corpus_mesh(topo.devices)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return compiled


@pytest.mark.parametrize("width", [32, 256], ids=["1word", "8words"])
def test_myers_distance_tiles(one_chip, width):
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    _compile(
        lambda q, ql, c, cl: pk.myers_distance_tiles(q, ql, c, cl,
                                                     interpret=False),
        sds((BUCKET, width)), sds((BUCKET,)), sds((CHUNK, width)),
        sds((CHUNK,)))


def test_jaro_winkler_sim_tiles(one_chip):
    def sds(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(
        lambda q, ql, c, cl, eq: pk.jaro_winkler_sim_tiles(
            q, ql, c, cl, eq, interpret=False),
        sds((BUCKET, CHARS)), sds((BUCKET,)), sds((CHUNK, CHARS)),
        sds((CHUNK,)), sds((BUCKET, CHUNK), jnp.bool_))


def test_set_intersection_tiles(one_chip):
    def sds(shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32, sharding=one_chip)

    _compile(
        lambda q, qn, c, cn: pk.set_intersection_tiles(q, qn, c, cn,
                                                       interpret=False),
        sds((BUCKET, 256)), sds((BUCKET,)), sds((CHUNK, 256)),
        sds((CHUNK,)))


def test_retrieval_segmax(one_chip):
    rows, queries, dim = 65536, 1024, 128

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(
        lambda qt, emb, enc, qrow, qgroup: pk.retrieval_segmax(
            qt, emb, enc, qrow, qgroup, tc=2048, seg=16,
            group_filtering=True, interpret=False),
        sds((dim, queries), jnp.bfloat16), sds((rows, dim), jnp.bfloat16),
        sds((rows, 128), jnp.int8), sds((1, queries), jnp.int32),
        sds((1, queries), jnp.int32))


def _stresstest_plan():
    """The Sesam dedup stresstest schema (benchmarks/f1_stresstest.py)."""
    schema = DukeSchema(
        threshold=0.8,
        maybe_threshold=None,
        properties=[
            Property(ID_PROPERTY_NAME, id_property=True),
            Property("name", C.Levenshtein(), 0.25, 0.85),
            Property("city", C.Exact(), 0.45, 0.65),
            Property("ssn", C.QGram(), 0.2, 0.9),
        ],
        data_sources=[],
    )
    return F.SchemaFeatures.plan(schema)


def _feature_avals(plan, rows: int, sharding):
    """Feature-tree avals at production widths, ``rows`` long."""
    record = Record()
    record.add_value(ID_PROPERTY_NAME, "r")
    widths = {"chars": CHARS, "grams": GRAMS}

    def aval(name, a):
        tail = (a.shape[1:-1] + (widths[name],) if name in widths
                else a.shape[1:])
        return jax.ShapeDtypeStruct((rows,) + tail, a.dtype,
                                    sharding=sharding)

    return {
        prop: {name: aval(name, a) for name, a in tensors.items()}
        for prop, tensors in F.extract_batch(plan, [record]).items()
    }


def _assert_fits(compiled):
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < HBM, total


def test_corpus_scorer_holds_mosaic_kernels(one_chip, monkeypatch):
    # the scorer picks the Pallas path from the backend it sees, which
    # here is the CPU: steer it to the chip's choice
    monkeypatch.setattr(pk, "_backend", lambda: "tpu")
    plan = _stresstest_plan()

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    scorer = S.build_corpus_scorer(plan, chunk=CHUNK, top_k=TOP_K,
                                   queries_from_rows=True)
    compiled = scorer.lower(
        {}, _feature_avals(plan, CAPACITY, one_chip),
        sds((CAPACITY,), np.bool_), sds((CAPACITY,), np.bool_),
        sds((CAPACITY,), np.int32), sds((BUCKET,), np.int32),
        sds((BUCKET,), np.int32), sds((), np.float32)).compile()
    # one Myers kernel for name, one intersection kernel for ssn
    assert compiled.as_text().count("tpu_custom_call") >= 2
    _assert_fits(compiled)


def test_mesh_programs_hold_mosaic_kernels(mesh4, monkeypatch):
    """The sharded-brute programs on a 4-chip mesh: GSPMD cannot
    partition a Mosaic kernel, so each must sit inside a shard_map."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from sesam_duke_microservice_tpu.parallel.sharded import (
        SHARD_AXIS,
        build_sharded_scorer,
        replicated_program,
    )

    monkeypatch.setattr(pk, "_backend", lambda: "tpu")
    plan = _stresstest_plan()
    rows = NamedSharding(mesh4, P(SHARD_AXIS))
    repl = NamedSharding(mesh4, P())

    def sds(shape, dtype, sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    scorer = build_sharded_scorer(plan, mesh4, chunk=CHUNK, top_k=TOP_K)
    compiled = scorer.lower(
        _feature_avals(plan, BUCKET, repl),
        _feature_avals(plan, CAPACITY, rows),
        sds((CAPACITY,), np.bool_, rows), sds((CAPACITY,), np.bool_, rows),
        sds((CAPACITY,), np.int32, rows), sds((BUCKET,), np.int32, repl),
        sds((BUCKET,), np.int32, repl), sds((), np.float32, repl)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 2
    _assert_fits(compiled)

    # the certified-finalize rescorer, over survivors gathered replicated
    dd = S.dd_rescorer(plan, queries_from_rows=False, value_slots_cap=8)
    names = {spec.name for spec in S.dd_plan_specs(plan)}
    gathered = {p: t for p, t in
                _feature_avals(plan, BUCKET * TOP_K, repl).items()
                if p in names}
    compiled = replicated_program(mesh4, dd).lower(
        _feature_avals(plan, BUCKET, repl), gathered,
        sds((BUCKET,), np.int32, repl),
        sds((BUCKET, TOP_K), np.int32, repl)).compile()
    assert "tpu_custom_call" in compiled.as_text()
