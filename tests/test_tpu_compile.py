"""Compile-only checks of the main-path Pallas kernels for a TPU v5e.

Interpret mode, which the other kernel tests use off TPU, accepts
programs that Mosaic refuses (unaligned reshapes, blocks over the
scoped VMEM limit). These tests lower and compile the kernels, at the
block sizes ``kernels/ops.py`` picks, for a described v5e chip with the
installed TPU compiler: nothing runs, so no chip is needed.

The topology is described inside a module fixture, never at import, so
every pytest-xdist worker collects the same tests. The persistent
compilation cache is off around them: a compile for a described chip
could be written to it but never read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.bandpass import bandpass_filter
from repro.kernels.fft_fourstep import fft_fourstep

HALF_8192 = 8192 // 2 + 1        # r2c half-spectrum width of an 8192² grid


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — any failure means: no topology
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compiled_text(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, jnp.float32, sharding=sharding)
            for s in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("rows", [8192, 200])
def test_bandpass_compiles_for_v5e(one_chip, rows):
    """8192 rows × the 8192² half-spectrum width needs a split row
    block (the whole plane does not fit VMEM); 200 rows fit whole."""
    br = ops.bandpass_block_rows(rows, HALF_8192)
    text = _compiled_text(
        lambda a, b, m: bandpass_filter(a, b, m, block_rows=br,
                                        interpret=False),
        one_chip, *[(rows, HALF_8192)] * 3)
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("inverse", [False, True])
@pytest.mark.parametrize("n", [256, 4096])
def test_fourstep_compiles_for_v5e(one_chip, n, inverse):
    B = 1024
    bb = ops.fft_block_b(B, n)
    text = _compiled_text(
        lambda a, b: fft_fourstep(a, b, inverse=inverse, block_b=bb,
                                  interpret=False),
        one_chip, (B, n), (B, n))
    assert "tpu_custom_call" in text


def test_stockham_still_refused_for_v5e(one_chip):
    """``ops.fft`` refuses ``kernel="stockham"`` on TPU and ``auto``
    takes four-step there, because Mosaic cannot lower the Stockham
    stage reshapes. If this compile starts to pass, that refusal and
    ``ops.STOCKHAM_REFUSED`` are stale."""
    from repro.kernels.fft_stockham import fft_stockham

    with pytest.raises(Exception, match="unsupported shape cast"):
        _compiled_text(lambda a, b: fft_stockham(a, b, interpret=False),
                       one_chip, (128, 128), (128, 128))
