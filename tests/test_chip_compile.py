"""Compile-only checks of the engine's Pallas kernels for a TPU v5e that is
described, not attached: the TPU compiler refuses here what interpret mode
cannot see (unsupported shape casts, unlowered primitives, VMEM overruns).
Each case compiles with ``interpret=False`` at the widths the round engine
uses; nothing runs, so these say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU compiler's library, and every test
worker imports this file."""
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import repro.core  # noqa: F401 — x64 on, exactly as the engine runs
from repro.kernels.range_scan.kernel import TILE_AUTO_THRESHOLD, range_scan_pallas
from repro.kernels.tree_descend import (
    descend_probe,
    descend_probe_pallas,
    frontier_compact_pallas,
)
from repro.kernels.tree_descend.ops import MAX_POOL_ROWS

B = 8  # tree fan-out of the TPU8 config
SEARCH_WIDTH = 16384  # point lanes per round in chip_smoke's store phase
SCAN_WIDTH = 2048  # pow2 range lanes of a 16384-lane YCSB-E-style round


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    # a compile for a described chip is written to an enabled persistent
    # cache but cannot be read back without the chip: keep it off here
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        jax.config.update("jax_enable_compilation_cache", enabled)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool_specs(sharding, rows, key_dtype=jnp.int32):
    return (
        _spec(sharding, (rows, B), key_dtype),  # keys
        _spec(sharding, (rows, B), key_dtype),  # vals
        _spec(sharding, (rows, B), jnp.int32),  # children
        _spec(sharding, (rows,), jnp.bool_),  # is_leaf
        _spec(sharding, (), jnp.int32),  # root
    )


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel in the program"
    return compiled


def test_descend_probe_compiles_at_max_pool(one_chip):
    """The fused descent+probe at the largest pool its dispatch gate
    admits, at a full store round's width."""
    compiled = _compile(
        lambda k, v, c, l, r, q: descend_probe_pallas(
            k, v, c, l, r, q, max_height=24, interpret=False
        ),
        *_pool_specs(one_chip, MAX_POOL_ROWS),
        _spec(one_chip, (SEARCH_WIDTH,), jnp.int32),
    )
    assert compiled.memory_analysis() is not None


def test_descend_probe_next_pool_size_is_refused(one_chip):
    """MAX_POOL_ROWS sits at the compiler's edge: the next capacity the
    pool grows to (double) does not fit VMEM."""
    with pytest.raises(Exception, match="vmem"):
        jax.jit(
            lambda k, v, c, l, r, q: descend_probe_pallas(
                k, v, c, l, r, q, max_height=24, interpret=False
            )
        ).lower(
            *_pool_specs(one_chip, 2 * (MAX_POOL_ROWS - 1) + 1),
            _spec(one_chip, (SEARCH_WIDTH,), jnp.int32),
        ).compile()


def test_engine_search_form_compiles(one_chip):
    """The form the round engine runs: the narrow dispatcher on the int64
    pool, vmapped over the shard axis (S = 1)."""
    one = lambda k, v, c, l, r, q: descend_probe(
        k, v, c, l, r, q, max_height=24, notfound=jnp.int64(-1),
        narrow=True, interpret=False,
    )
    stacked = [
        _spec(one_chip, (1,) + s.shape, s.dtype)
        for s in _pool_specs(one_chip, MAX_POOL_ROWS, jnp.int64)
    ]
    _compile(jax.vmap(one), *stacked, _spec(one_chip, (1, SEARCH_WIDTH), jnp.int64))


@pytest.mark.parametrize("frontier", [8, 16, 32, 64])
@pytest.mark.parametrize("variant", ["pairwise", "tiled"])
def test_range_scan_compiles_at_scan_widths(one_chip, frontier, variant):
    """Both rank-select variants at every leaf-frontier width a scan of up
    to ~100 records doubles through (n = frontier · b candidates)."""
    n = frontier * B
    tile_n = -1 if variant == "pairwise" else 128
    _compile(
        lambda k, v, lo, hi: range_scan_pallas(
            k, v, lo, hi, cap=128, tile_n=tile_n, interpret=False
        ),
        _spec(one_chip, (SCAN_WIDTH, n), jnp.int32),
        _spec(one_chip, (SCAN_WIDTH, n), jnp.int32),
        _spec(one_chip, (SCAN_WIDTH,), jnp.int32),
        _spec(one_chip, (SCAN_WIDTH,), jnp.int32),
    )


def test_range_scan_auto_variant_switch():
    """The widths above cover both sides of the auto switch."""
    widths = [f * B for f in (8, 16, 32, 64)]
    assert min(widths) <= TILE_AUTO_THRESHOLD < max(widths)


@pytest.mark.parametrize("frontier", [8, 64])
def test_frontier_compact_compiles(one_chip, frontier):
    """The scan descent's per-level compaction: M = frontier·(b+1)
    candidates per lane into a width-``frontier`` frontier."""
    m = frontier * (B + 1)
    _compile(
        lambda c, v: frontier_compact_pallas(c, v, f=frontier, interpret=False),
        _spec(one_chip, (SCAN_WIDTH, m), jnp.int32),
        _spec(one_chip, (SCAN_WIDTH, m), jnp.bool_),
    )


def test_interpret_mode_is_cpu_only():
    """The one place interpret mode is decided: on for the CPU backend only,
    and an explicit request off the CPU is refused."""
    from repro.kernels import interpret_mode

    on_cpu = jax.default_backend() == "cpu"
    assert interpret_mode() is on_cpu
    assert interpret_mode(False) is False
    if on_cpu:
        assert interpret_mode(True) is True
    else:
        with pytest.raises(ValueError):
            interpret_mode(True)
