"""The main-path tiered-gather kernels, and the dense chunk step, compile
for a TPU v5e.

Interpret mode accepts block shapes the TPU compiler refuses, so every
entry point of ``kernels/tiered_gather`` is compiled here, with
``interpret=False``, for a described (not attached) v5e chip: at the row
width of smollm-360m at full width (2 * 32 layers * 5 kv heads * 64 =
20480) and at the 128-wide recurrent payload. The store sizes are those
``chip_smoke.py`` serves with (2048 pages, 30% near, a 1024-gather step).
The serving engine's chunk step for smollm-360m is compiled at the size the
benchmark serves it (16 slots of 2048 positions, 128-token chunks).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.tiered_gather.ops import (
    LOOKUP_KERNEL,
    SEGMENTED_KERNEL,
    gather_rows,
    tiered_lookup_counted,
    tiered_lookup_segments,
)

N_PAGES = 2048
N_NEAR = 614  # int(0.30 * N_PAGES)
N_IDS = 1024
N_SEGMENTS = 9  # max_batch 8 + the padding segment


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def _store_args(shape, d):
    return (
        shape((N_NEAR, d), jnp.float32),  # near rows
        shape((N_PAGES, d), jnp.int8),  # far rows
        shape((N_PAGES,), jnp.float32),  # far scales
        shape((N_PAGES,), jnp.int32),  # tier map
        shape((N_PAGES,), jnp.int32),  # slot map
        shape((N_IDS,), jnp.int32),  # page ids
    )


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


D_PARAMS = pytest.mark.parametrize("d", [20480, 128])


@D_PARAMS
def test_tiered_lookup_segments_compiles(shape, d):
    args = _store_args(shape, d) + (shape((N_IDS,), jnp.int32),)
    fn = functools.partial(
        tiered_lookup_segments, n_segments=N_SEGMENTS, interpret=False
    )
    compiled = _compile(fn, args)
    # the step's rows come back at full width: nothing is lost to padding
    rows, hits = compiled.out_info
    assert rows.shape == (N_IDS, d) and hits.shape == (N_SEGMENTS, 2)
    mem = compiled.memory_analysis()
    store_bytes = N_NEAR * d * 4 + N_PAGES * d
    # the (M, 1, D) row views may be relaid out per dispatch; what that
    # costs must stay within a small multiple of the stores themselves
    assert mem.temp_size_in_bytes <= 8 * store_bytes, mem


@D_PARAMS
def test_tiered_lookup_counted_compiles(shape, d):
    fn = functools.partial(tiered_lookup_counted, interpret=False)
    compiled = _compile(fn, _store_args(shape, d))
    rows, near, far = compiled.out_info
    assert rows.shape == (N_IDS, d) and near.shape == far.shape == ()


@pytest.mark.parametrize(
    "entry,kernel",
    [("segments", SEGMENTED_KERNEL), ("counted", LOOKUP_KERNEL)],
)
def test_tiered_gather_kernels_carry_their_names(shape, entry, kernel):
    """The v5e lowering names each tiered-gather kernel's operation after
    the kernel, so a device trace shows it under that name."""
    args = _store_args(shape, 128)
    if entry == "segments":
        fn = functools.partial(
            tiered_lookup_segments, n_segments=N_SEGMENTS, interpret=False
        )
        args += (shape((N_IDS,), jnp.int32),)
    else:
        fn = functools.partial(tiered_lookup_counted, interpret=False)
    kernel_ops = [
        line.split(" = ", 1)[0].strip().lstrip("%")
        for line in _compile(fn, args).as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    assert len(kernel_ops) == 1, kernel_ops
    assert kernel_ops[0].split(".")[0] == kernel


@D_PARAMS
@pytest.mark.parametrize("dequant", [False, True], ids=["plain", "dequant"])
def test_gather_rows_compiles(shape, d, dequant):
    if dequant:
        src = shape((N_PAGES, d), jnp.int8)
        scales = shape((N_PAGES,), jnp.float32)
        fn = functools.partial(gather_rows, interpret=False)
        args = (src, shape((N_IDS,), jnp.int32), scales)
    else:
        # the flat f32 mirror the verify path reads
        fn = functools.partial(gather_rows, interpret=False)
        args = (shape((N_PAGES, d), jnp.float32), shape((N_IDS,), jnp.int32))
    compiled = _compile(fn, args)
    assert compiled.out_info.shape == (N_IDS, d)


def test_dense_chunk_step_compiles(shape):
    """The dense chunk step — one (B, C) block pass — compiles for one v5e
    at smollm-360m's full width, keeps the name the device trace finds it
    by, and holds at most one extra copy of the slot cache in temporaries
    (the column scan it replaced needed about ten)."""
    from repro.configs import get_config
    from repro.models.api import get_model
    from repro.runtime.serving import make_chunk_step

    b, max_len, c = 16, 2048, 128
    api = get_model(get_config("smollm-360m"))
    assert api.block_decode

    def place(tree):
        return jax.tree.map(lambda x: shape(x.shape, x.dtype), tree)

    cache = place(api.abstract_cache(b, max_len))
    masks = [shape((b, c), jnp.bool_)] * 3
    compiled = (
        jax.jit(make_chunk_step(api), donate_argnums=(1,))
        .lower(place(api.abstract_params()), cache, shape((b,), jnp.int32),
               shape((b, c), jnp.int32), *masks)
        .compile()
    )
    assert compiled.as_text().startswith("HloModule jit__chunk_step")
    cache_bytes = sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(cache))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes < 2 * cache_bytes, mem
