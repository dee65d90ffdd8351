"""Compile every main-path byte kernel for a described TPU v5e chip.

Interpret mode accepts block shapes, dtypes and VMEM footprints that the
chip's Mosaic compiler refuses, so the CPU tests alone cannot show that a
kernel runs on the chip. These tests lower each kernel at real shapes —
the 64 KiB-bucket ragged batches, and the row-groups ``pack_plan`` cuts
from a Common-Crawl-like length distribution (narrowest and widest width
bucket, payloads up to the 1 MiB truncation limit, a 3·2^k row count) —
and compile them for one chip of a ``v5e:2x2`` topology described, not
attached. Nothing runs; a refusal raises here instead of on the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels
from repro.columnar.store import pack_plan
from repro.kernels.adler32.adler32 import adler32_partials_batch
from repro.kernels.bucketing import ROWGROUP_PAD, bucket_width
from repro.kernels.digest_sig import HPAD, digest_sig_partials_batch
from repro.kernels.digest_sig.digest_sig import MAX_NGRAM
from repro.kernels.pattern_scan.pattern_scan import (
    DEFAULT_BLOCK,
    MAX_PATTERN,
    pattern_scan_batch_multi,
    pattern_scan_rowgroup,
)

MIB = 1 << 20


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the kernels steered onto
    their compiled (non-interpret) path and the persistent cache off (a
    described-chip compile written there cannot be read back)."""
    pytest.importorskip("libtpu", reason="libtpu (the TPU compiler) is "
                        "not installed")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        mp.setattr(repro.kernels, "interpret_mode", lambda: False)
        cache_was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        jax.clear_caches()  # no CPU-traced (interpreted) kernel is reused
        try:
            topo = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.clear_caches()
            jax.config.update("jax_enable_compilation_cache", cache_was)


def _rowgroup_shapes() -> list[tuple[int, int]]:
    """(padded_rows, width) of the narrowest and widest row-groups and of
    a 3·2^k-row group, from a seeded Common-Crawl-like corpus: small
    request/metadata records plus log-normal responses truncated at
    1 MiB."""
    rng = np.random.default_rng(0)
    small = rng.integers(150, 700, 40_000)
    responses = np.minimum(rng.lognormal(9.8, 1.2, 20_000), MIB)
    lengths = np.concatenate([small, responses.astype(np.int64), [MIB]])
    plan = pack_plan(lengths)
    shapes = {(g.padded_rows, g.width) for g in plan}
    narrow = min(shapes, key=lambda s: (s[1], -s[0]))
    wide = max(shapes, key=lambda s: s[1])
    three = next(s for s in sorted(shapes)
                 if s[0] >= 24 and s[0] % 3 == 0)
    assert wide[1] == MIB and narrow[1] == 256
    return [narrow, three, wide]


ROWGROUPS = _rowgroup_shapes()


def _compile(fn, one_chip, *shapes, **static):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(lambda *a: fn(*a, **static)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # a Mosaic kernel
    return compiled


@pytest.mark.parametrize("rows,width,n",
                         [(*rg, 5) for rg in ROWGROUPS]
                         + [(*ROWGROUPS[-1], MAX_NGRAM)])
def test_digest_sig_rowgroup_compiles(one_chip, rows, width, n):
    """Default n-gram at every row-group shape, and the longest n-gram
    the kernel takes (the largest scoped-VMEM footprint) at the widest."""
    _compile(digest_sig_partials_batch, one_chip,
             ((rows, width + HPAD), jnp.uint8), n=n, block=min(2048, width))


@pytest.mark.parametrize("rows,width", ROWGROUPS)
def test_pattern_scan_rowgroup_compiles(one_chip, rows, width):
    _compile(pattern_scan_rowgroup, one_chip,
             ((rows, width + ROWGROUP_PAD), jnp.uint8),
             ((MAX_PATTERN,), jnp.int32), pat_len=MAX_PATTERN)


@pytest.mark.parametrize("rows,size", [(64, 60_000), (12, MIB)])
def test_pattern_scan_batches_compile(one_chip, rows, size):
    """The ragged-batch path's 64 KiB buckets, single- and per-row
    pattern."""
    width = bucket_width(size, DEFAULT_BLOCK)
    mat = ((rows, width + ROWGROUP_PAD), jnp.uint8)
    _compile(pattern_scan_rowgroup, one_chip, mat,
             ((MAX_PATTERN,), jnp.int32), pat_len=4)
    _compile(pattern_scan_batch_multi, one_chip, mat,
             ((rows, MAX_PATTERN), jnp.int32), max_len=MAX_PATTERN)


@pytest.mark.parametrize("rows,size", [(48, 9_000), (12, MIB)])
def test_adler32_batch_compiles(one_chip, rows, size):
    _compile(adler32_partials_batch, one_chip,
             ((rows, bucket_width(size, 2048)), jnp.uint8))
