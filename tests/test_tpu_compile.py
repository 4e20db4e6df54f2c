"""Main-path programs compiled for a described TPU v5e at real widths.

Nothing runs: the TPU compiler installed beside JAX compiles for a v5e:2x2
topology that is described, not attached, and refuses what the chip would
refuse (layouts, memory, partitioning). The topology is built inside a
module fixture, never at import, so only the xdist worker that runs this
file loads libtpu.
"""

import os

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

N_1KG = 2504  # 1000 Genomes phase 3 cohort
N_LARGE = 25_000  # the large-cohort cell
BLOCK = 16384  # the wgs-batch traffic's block size (benchmark/traffic)


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without the chip; keep these compiles out of it.
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _about(nbytes, logical):
    """Device bytes match ``logical`` up to the chip's tile padding (the
    minor dimension rounds up to a multiple of 128 lanes)."""
    return logical <= nbytes < 1.05 * logical


@pytest.mark.parametrize(
    "operand, accum",
    [(np.int8, jnp.int32), (ml_dtypes.bfloat16, jnp.float32)],
    ids=["int8", "bf16"],
)
def test_dense_update_compiles(one_chip, operand, accum):
    """The host-fed dense Gramian update (bit-packed block in, unpack + dot
    on device) at N=2504, B=16384, in both MXU operand dtypes."""
    from spark_examples_tpu.ops.gramian import _dense_update

    G = _spec((1, N_1KG, N_1KG), accum, one_chip)
    X = _spec((1, BLOCK, -(-N_1KG // 8)), jnp.uint8, one_chip)
    compiled = _dense_update.lower(
        G, X, operand_dtype=operand, num_samples=N_1KG
    ).compile()
    mem = compiled.memory_analysis()
    assert _about(mem.output_size_in_bytes, N_1KG * N_1KG * 4)


def _whole_genome_update(one_chip):
    """The fused generate→accumulate scan at the whole-genome dispatch
    geometry (spacing 73, B=16384, the auto dispatch length) and its
    arguments' shapes on one described chip."""
    from spark_examples_tpu.ops.devicegen import _fused_update, auto_blocks_per_dispatch
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    source = SyntheticGenomicsSource(num_samples=N_1KG, seed=42, variant_spacing=73)
    K = auto_blocks_per_dispatch(N_1KG, BLOCK)
    with jax.enable_x64(True):
        update = _fused_update(
            (source.genotype_stream_key("bench-1kg"),),
            np.asarray(source.populations, np.int32).tobytes(),
            int(source.site_key),
            73,
            float(source.ref_block_fraction),
            None,
            BLOCK,
            K,
            "int8",
            "int32",
            int(source.n_pops),
            None,
        )
        scalar = _spec((), jnp.int64, one_chip)
        shapes = (
            _spec((N_1KG, N_1KG), jnp.int32, one_chip),
            _spec((1,), jnp.int64, one_chip),
            scalar,
            scalar,
            scalar,
        )
    return update, shapes


def test_devicegen_whole_genome_update_compiles(one_chip):
    update, shapes = _whole_genome_update(one_chip)
    with jax.enable_x64(True):
        compiled = update.lower(*shapes).compile()
    assert compiled.memory_analysis().output_size_in_bytes >= N_1KG * N_1KG * 4


def _instructions(hlo_text):
    """``{instruction name: its line}`` of a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        text = line.strip()
        if " = " in text:
            out[text.split(" = ", 1)[0].removeprefix("ROOT ").lstrip("%")] = text
    return out


def _computations(hlo_text):
    """``{computation name: its instruction lines}`` of a compiled module."""
    out, body = {}, None
    for line in hlo_text.splitlines():
        text = line.strip()
        if text.endswith("{") and " = " not in text:
            words = text.split()
            body = out.setdefault(words[1 if words[0] == "ENTRY" else 0].lstrip("%"), [])
        elif body is not None and " = " in text:
            body.append(text)
    return out


def test_devicegen_update_op_scopes_split_generation_from_the_dot(one_chip):
    """The scope map of the whole-genome update as the chip compiles it:
    the int8 dot (every convolution) is ``int8_dot``; one ``generate``
    fusion evaluates the genotype hash over all 2,504 columns and writes
    the int8 operand itself, with no concatenation of population segments
    (their per-column thresholds are selected inside the hash)."""
    from spark_examples_tpu.ops import devicegen

    update, shapes = _whole_genome_update(one_chip)
    saved = dict(devicegen._DISPATCHED)
    devicegen._DISPATCHED.clear()
    try:
        devicegen._note_dispatch(update, shapes)
        scopes = devicegen.update_op_scopes()
        text = devicegen._compiled_text(update, shapes)
    finally:
        devicegen._DISPATCHED.clear()
        devicegen._DISPATCHED.update(saved)
    assert list(scopes) == ["jit_devicegen_update"]
    scope = scopes["jit_devicegen_update"]
    lines = _instructions(text)

    def named(test):
        found = [name for name, line in lines.items() if test(name, line.split(" = ", 1)[1])]
        assert found
        return {scope.get(name) for name in found}

    assert named(lambda n, rhs: "convolution" in n or " convolution(" in rhs) == {"int8_dot"}
    assert set(scope.values()) == {"generate", "int8_dot", "count"}

    # The scan body: the computation that calls the dot's fusion.
    bodies = _computations(text)

    def called(line):
        return line.split("calls=")[1].split(",")[0].lstrip("%") if "calls=" in line else None

    dot = next(name for name, ops in bodies.items() if any(" convolution(" in op for op in ops))
    (body,) = [ops for ops in bodies.values() if any(called(op) == dot for op in ops)]
    assert not [
        op for op in body
        if " concatenate(" in op and f"[{BLOCK}," in op.split(" concatenate(")[0]
    ]
    hashes = [
        op for op in body
        if " fusion(" in op
        and any(
            f"u32[{BLOCK},{N_1KG}]" in inner and " multiply(" in inner
            for inner in bodies[called(op)]
        )
    ]
    assert len(hashes) == 1
    (operand,) = [
        op.split(" = ", 1)[0].lstrip("%")
        for op in body
        if " fusion(" in op and op.split(" = ", 1)[1].startswith(f"s8[{BLOCK},{N_1KG}]")
    ]
    assert hashes[0].split(" = ", 1)[0].lstrip("%") == operand
    assert scope[operand] == "generate"


def test_ring_update_op_scopes_hold_the_ring_exchange():
    """On four virtual CPU devices: the ring program is named
    ``jit_devicegen_ring_update`` and its tile ``ppermute`` is scoped
    ``ring_exchange``."""
    from spark_examples_tpu.ops import devicegen
    from spark_examples_tpu.parallel.mesh import SAMPLES_AXIS, make_mesh
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    if jax.devices()[0].platform != "cpu" or jax.device_count() < 4:
        pytest.skip("needs four virtual CPU devices")
    source = SyntheticGenomicsSource(num_samples=18, seed=9)
    acc = devicegen.DeviceGenRingGramianAccumulator(
        num_samples=18,
        vs_key=source.genotype_stream_key("vs"),
        pops=source.populations,
        site_key=source.site_key,
        spacing=source.variant_spacing,
        ref_block_fraction=source.ref_block_fraction,
        mesh=make_mesh({SAMPLES_AXIS: 4}),
        block_size=16,
        blocks_per_dispatch=2,
    )
    acc.add_grid(0, 40)
    scopes = devicegen.update_op_scopes()["jit_devicegen_ring_update"]
    assert {"generate", "int8_dot", "count", "ring_exchange"} <= set(scopes.values())


def test_finalize_compiles(one_chip):
    """Gower centering (f64 arithmetic under x64) and the subspace
    eigensolve of the 2504² Gramian."""
    from spark_examples_tpu.ops.centering import gower_center
    from spark_examples_tpu.ops.pca import principal_components_subspace

    with jax.enable_x64(True):
        gower_center.lower(_spec((N_1KG, N_1KG), jnp.int32, one_chip)).compile()
    compiled = principal_components_subspace.lower(
        _spec((N_1KG, N_1KG), jnp.float32, one_chip), num_pc=2
    ).compile()
    assert compiled.memory_analysis().output_size_in_bytes < N_1KG * 64


@pytest.mark.parametrize("shape", [(1, 4), (2, 2)], ids=["1x4", "2x2"])
def test_sharded_update_compiles_per_device(topo, shape):
    """The packed ring update at N=25,000 over four described chips: each
    device holds one row tile of the Gramian, and the ring is
    collective-permutes."""
    from spark_examples_tpu.ops.gramian import build_sharded_update
    from spark_examples_tpu.parallel.mesh import DATA_AXIS, SAMPLES_AXIS, padded_cohort

    data, samples = shape
    mesh = Mesh(np.array(topo.devices).reshape(shape), (DATA_AXIS, SAMPLES_AXIS))
    padded = padded_cohort(N_LARGE, samples, pack=True)
    G = _spec(
        (data, padded, padded),
        jnp.int32,
        NamedSharding(mesh, P(DATA_AXIS, SAMPLES_AXIS, None)),
    )
    X = _spec(
        (data, 1024, padded // 8),
        jnp.uint8,
        NamedSharding(mesh, P(DATA_AXIS, None, SAMPLES_AXIS)),
    )
    compiled = build_sharded_update(mesh, np.int8, True).lower(G, X).compile()
    tile = padded * padded * 4 // samples
    assert _about(compiled.memory_analysis().output_size_in_bytes, tile)
    assert "collective-permute" in compiled.as_text()


def test_devicegen_half_ring_compiles_without_slice_writes(topo):
    """The device-generation ring at 50,000 samples on a described 1x4:
    each block runs two tile permutes and three int8 dots, each dot and
    its add one fusion into its own step tile, and no slice of the state
    is written back (the full ring's column writes were 17% of its
    update). One copy of the state is three 12,504² int32 tiles; the
    tiles' stacking for the ring's entry point compiles away."""
    from spark_examples_tpu.ops.devicegen import _ring_update
    from spark_examples_tpu.parallel.mesh import DATA_AXIS, SAMPLES_AXIS, padded_cohort
    from spark_examples_tpu.sources.synthetic import SyntheticGenomicsSource

    n, steps = 50_000, 3
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), (DATA_AXIS, SAMPLES_AXIS))
    padded = padded_cohort(n, 4, pack=True)
    n_local = padded // 4
    source = SyntheticGenomicsSource(num_samples=n, seed=42, variant_spacing=73)
    pops = np.zeros(padded, np.int32)
    pops[:n] = source.populations
    scalar = NamedSharding(mesh, P(DATA_AXIS))
    with jax.enable_x64(True):
        update = _ring_update.__wrapped__(
            (source.genotype_stream_key("x"),), pops.tobytes(), int(source.site_key), 73,
            float(source.ref_block_fraction), None, BLOCK, 32, "int8", n, padded,
            int(source.n_pops), mesh, None, True,
        )
        tile = _spec((1, padded, n_local), jnp.int32,
                     NamedSharding(mesh, P(DATA_AXIS, SAMPLES_AXIS, None)))
        compiled = update.lower(
            (tile,) * steps,
            _spec((1, 1), jnp.int64, NamedSharding(mesh, P(DATA_AXIS, None))),
            _spec((1,), jnp.int64, scalar),
            _spec((1,), jnp.int64, scalar),
            _spec((1,), jnp.int64, scalar),
        ).compile()
    assert _about(compiled.memory_analysis().output_size_in_bytes, steps * n_local * n_local * 4)
    bodies = _computations(compiled.as_text())
    ops = [op for body in bodies.values() for op in body]
    assert sum(" collective-permute-start(" in op for op in ops) == 2
    assert sum(" convolution(" in op for op in ops) == steps
    assert not [op for op in ops if " dynamic-update-slice(" in op or " concatenate(" in op]
