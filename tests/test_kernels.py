"""Per-kernel tests: shape/dtype sweeps vs the ref.py oracles (interpret mode)."""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.kernels.adler32 import adler32
from repro.kernels.adler32.ref import adler32_jnp, adler32_zlib
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.pattern_scan import (
    count_matches,
    find_pattern_mask,
    find_pattern_mask_batch,
    find_pattern_masks_multi,
    find_pattern_positions,
)
from repro.kernels.digest_sig import (
    digest_signature_batch,
    digest_signature_reference,
)
from repro.kernels.pattern_scan.ref import pattern_mask_ref


# --------------------------------------------------------------------------
# interpret mode and the compile cache
# --------------------------------------------------------------------------

@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None), ("rocm", None)])
def test_interpret_mode_follows_backend(monkeypatch, backend, interpret):
    import repro.kernels

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match=backend):
            repro.kernels.interpret_mode()
    else:
        assert repro.kernels.interpret_mode() is interpret


def test_compile_cache_directory(tmp_path):
    """JAX_COMPILATION_CACHE_DIR places the persistent cache; unset, it is
    the checkout's fixed .jax_cache. Compiled programs land there whether
    JAX was imported before the call (config) or after it (environment)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    import repro.kernels

    compile_ = ("jax.jit(lambda x: x * 3 + 1)(jax.numpy.arange(5.0))"
                ".block_until_ready()")
    jax_first = ("import jax, repro.kernels as k; "
                 f"print(k.enable_compile_cache()); {compile_}")
    jax_after = ("import repro.kernels as k; p = k.enable_compile_cache(); "
                 f"import jax; {compile_}; print(p)")
    root = Path(repro.kernels.__file__).resolve().parents[3]
    env = {**os.environ, "PYTHONPATH": str(root / "src")}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    for i, script in enumerate((jax_first, jax_after)):
        where = tmp_path / f"jc{i}"
        for extra, expect in (({"JAX_COMPILATION_CACHE_DIR": str(where)},
                               where), ({}, root / ".jax_cache")):
            out = subprocess.run(
                [sys.executable, "-c", script], env={**env, **extra},
                check=True, capture_output=True, text=True, timeout=120)
            assert out.stdout.split()[-1] == str(expect)
            assert any(expect.iterdir())


# --------------------------------------------------------------------------
# pattern_scan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("pattern", [b"\r\n", b"\r\n\r\n", b"WARC/", b"X"])
@pytest.mark.parametrize("size", [0, 1, 63, 1024, 70_000])
def test_pattern_scan_shape_sweep(pattern, size):
    rng = np.random.default_rng(size + len(pattern))
    buf = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
    got = find_pattern_mask(buf, pattern, block=1024)
    ref = np.asarray(pattern_mask_ref(
        np.frombuffer(buf, np.uint8), np.frombuffer(pattern, np.uint8)))
    np.testing.assert_array_equal(got, ref[:len(got)])


def test_pattern_scan_finds_warc_delimiters():
    from repro.data.synth import CorpusSpec, generate_warc
    data = generate_warc(CorpusSpec(n_pages=5, seed=1), "none")
    hdr_ends = find_pattern_positions(data, b"\r\n\r\n")
    magics = find_pattern_positions(data, b"WARC/1.1")
    # one magic per record; every magic is followed by a header terminator
    assert len(magics) == 16  # warcinfo + 5 * (req, resp, meta)
    for m in magics:
        assert any(h > m for h in hdr_ends)


@given(st.binary(max_size=512), st.binary(min_size=1, max_size=8))
@settings(max_examples=60, deadline=None)
def test_pattern_scan_property(buf, pattern):
    if not any(pattern):
        return  # all-zero patterns rejected by design (zero padding)
    got = find_pattern_positions(buf, pattern, block=256)
    # Python oracle
    expect, i = [], buf.find(pattern)
    while i >= 0:
        expect.append(i)
        i = buf.find(pattern, i + 1)
    assert list(got) == expect


def test_pattern_scan_count():
    buf = b"ab" * 1000
    assert count_matches(buf, b"ab", block=512) == 1000


def test_multi_pattern_batch_equals_per_pattern():
    """Per-row-pattern dispatch == N single-pattern dispatches (the
    cross-request batching primitive must not change any mask)."""
    rng = np.random.default_rng(11)
    bufs = [rng.integers(0, 256, n, np.uint8).tobytes()
            for n in (0, 1, 77, 1500, 4096, 9000)]
    bufs[2] = b"needle" + bufs[2] + b"needle"
    pats = [b"X", b"\r\n\r\n", b"needle", b"ab", b"0123456789abcdef", b"q"]
    multi = find_pattern_masks_multi(bufs, pats, block=1024)
    for buf, pat, got in zip(bufs, pats, multi):
        single = find_pattern_mask_batch([buf], pat, block=1024)[0]
        np.testing.assert_array_equal(got, single)


def test_multi_pattern_mixed_lengths_share_bucket():
    """Different-length patterns in one width bucket stay independent:
    the padded compare positions of a short pattern must not leak into
    its neighbours' rows."""
    base = b"abcabcabc--zzzz"
    bufs = [base * 20, base * 20, base * 20]
    pats = [b"abc", b"abcabcabc--zzz", b"zz"]
    multi = find_pattern_masks_multi(bufs, pats, block=256)
    for buf, pat, got in zip(bufs, pats, multi):
        expect, i = [], buf.find(pat)
        while i >= 0:
            expect.append(i)
            i = buf.find(pat, i + 1)
        assert list(np.flatnonzero(got)) == expect, pat


def test_multi_pattern_rejects_mismatched_inputs():
    with pytest.raises(ValueError, match="pair up"):
        find_pattern_masks_multi([b"abc"], [b"a", b"b"])
    with pytest.raises(ValueError, match="all-zero"):
        find_pattern_masks_multi([b"abc"], [b"\x00\x00"])


# --------------------------------------------------------------------------
# adler32
# --------------------------------------------------------------------------

@pytest.mark.parametrize("size", [0, 1, 7, 2048, 2049, 65536, 1_000_003])
def test_adler32_size_sweep(size):
    rng = np.random.default_rng(size)
    data = rng.integers(0, 256, size=size, dtype=np.uint8).tobytes()
    assert adler32(data) == (zlib.adler32(data) & 0xFFFFFFFF)


@given(st.binary(max_size=8192))
@settings(max_examples=100, deadline=None)
def test_adler32_property(data):
    expected = zlib.adler32(data) & 0xFFFFFFFF
    assert adler32(data) == expected
    assert adler32_jnp(np.frombuffer(data, np.uint8)) == expected


def test_adler32_block_size_invariance():
    data = np.random.default_rng(3).integers(0, 256, 10_000, np.uint8).tobytes()
    for block in (256, 1024, 2048):
        assert adler32(data, block=block) == adler32_zlib(data)


# --------------------------------------------------------------------------
# batched dispatch (one gridded pallas_call for a ragged payload batch)
# --------------------------------------------------------------------------

def _ragged_payloads(seed, sizes):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, size=s, dtype=np.uint8).tobytes()
            for s in sizes]


def test_adler32_batch_matches_zlib_ragged():
    from repro.kernels.adler32 import adler32_batch
    payloads = _ragged_payloads(0, [0, 1, 7, 100, 2048, 2049, 5000, 65_537])
    got = adler32_batch(payloads, block=1024)
    assert got.dtype == np.uint32 and got.shape == (len(payloads),)
    for checksum, p in zip(got, payloads):
        assert int(checksum) == (zlib.adler32(p) & 0xFFFFFFFF)


def test_adler32_batch_empty_and_singleton():
    from repro.kernels.adler32 import adler32_batch
    assert adler32_batch([]).shape == (0,)
    data = b"warc record payload"
    assert int(adler32_batch([data])[0]) == (zlib.adler32(data) & 0xFFFFFFFF)


def test_adler32_batch_skewed_widths_bucketed():
    # one giant outlier must not inflate every row's padding; results
    # stay exact across the width buckets
    from repro.kernels.adler32 import adler32_batch
    payloads = _ragged_payloads(5, [100] * 6 + [300_000] + [2048] * 3)
    got = adler32_batch(payloads, block=2048)
    for checksum, p in zip(got, payloads):
        assert int(checksum) == (zlib.adler32(p) & 0xFFFFFFFF)


def test_verify_digest_malformed_value_is_false():
    from repro.core.warc.checksum import verify_digest, verify_digests_bulk
    data = b"payload"
    for header in ("adler32:zzzz", "crc32:not-hex", "adler32:"):
        assert verify_digest(data, header) is False
        assert verify_digests_bulk([data], [header]) == [False]
        assert verify_digests_bulk([data], [header],
                                   use_kernel=False) == [False]


def test_adler32_batch_matches_looped_single():
    from repro.kernels.adler32 import adler32_batch
    payloads = _ragged_payloads(7, [513, 1, 4096, 2047])
    batched = adler32_batch(payloads, block=512)
    looped = [adler32(p, block=512) for p in payloads]
    assert [int(c) for c in batched] == looped


def test_pattern_scan_batch_matches_single_and_ref():
    from repro.kernels.pattern_scan import find_pattern_mask_batch
    pattern = b"\r\n\r\n"
    bufs = _ragged_payloads(11, [0, 3, 512, 1025, 70_000])
    bufs.append(b"x\r\n\r\ny" * 200)
    masks = find_pattern_mask_batch(bufs, pattern, block=1024)
    assert len(masks) == len(bufs)
    for mask, buf in zip(masks, bufs):
        assert mask.shape == (len(buf),)
        single = find_pattern_mask(buf, pattern, block=1024)
        np.testing.assert_array_equal(mask, single)
        ref = np.asarray(pattern_mask_ref(
            np.frombuffer(buf, np.uint8), np.frombuffer(pattern, np.uint8)))
        np.testing.assert_array_equal(mask, ref[:len(mask)])


def test_pattern_scan_batch_width_bucketing():
    # half-step width buckets (parity with adler32_batch): outliers
    # don't inflate every row, and bucketed results equal unbucketed
    from repro.kernels.bucketing import bucket_width, quantize_count
    from repro.kernels.pattern_scan import find_pattern_mask_batch

    block = 512
    assert bucket_width(0, block) == block
    assert bucket_width(block, block) == block
    assert bucket_width(block + 1, block) == 2 * block
    # half-step ladder: 3 blocks is its own bucket now (was 4 under pow2)
    assert bucket_width(3 * block, block) == 3 * block
    assert bucket_width(3 * block + 1, block) == 4 * block
    assert bucket_width(5 * block, block) == 6 * block
    assert [quantize_count(n) for n in range(1, 14)] == \
        [1, 2, 3, 4, 6, 6, 8, 8, 12, 12, 12, 12, 16]
    # worst-case pad per dimension is bounded by 1.5x
    assert all(quantize_count(n) <= 1.5 * n for n in range(1, 10000))
    sizes = [1, 100, 511, 512, 513, 2000, 5000, 9000]
    bufs = _ragged_payloads(13, sizes)
    assert len({bucket_width(len(b), block) for b in bufs}) > 1
    masks = find_pattern_mask_batch(bufs, b"\r\n", block=block)
    for mask, buf in zip(masks, bufs):  # order preserved across buckets
        assert mask.shape == (len(buf),)
        np.testing.assert_array_equal(
            mask, find_pattern_mask(buf, b"\r\n", block=block))


def test_pattern_scan_batch_cross_tile_matches():
    # matches straddling tile boundaries exercise the explicit halo input
    from repro.kernels.pattern_scan import find_pattern_mask_batch
    block = 256
    buf = bytearray(4 * block)
    for pos in (block - 1, block - 3, 2 * block - 2, 3 * block - 1):
        buf[pos:pos + 4] = b"ABCD"
    masks = find_pattern_mask_batch([bytes(buf)], b"ABCD", block=block)
    assert sorted(np.flatnonzero(masks[0]).tolist()) == [
        block - 3, 2 * block - 2, 3 * block - 1]


@pytest.mark.parametrize("nrows,width", [(1, 256), (12, 768), (24, 6144),
                                         (1024, 256), (6, 1 << 20)])
def test_grid_tiles_are_legal_tpu_blocks(nrows, width):
    """Every tile is a legal Mosaic block (rows a multiple of 8 or the
    whole array, cols a lane multiple dividing the width) inside the
    byte budget wherever the shape allows."""
    from repro.kernels.bucketing import LANES, SUBLANES, grid_tiles

    budget = 1 << 16
    rows, cols = grid_tiles(nrows, width, unit=LANES, budget=budget)
    assert nrows % rows == 0 and (rows % SUBLANES == 0 or rows == nrows)
    assert width % cols == 0 and cols % LANES == 0
    padded_rows = -(-rows // SUBLANES) * SUBLANES
    assert padded_rows * cols <= budget or cols == LANES or \
        (rows == nrows and nrows % SUBLANES)


def test_rowgroup_scan_and_digest_match_ragged_batch():
    """The packed row-group entry points (a 3·2^k-row group several grid
    steps wide) agree with the ragged-batch wrappers."""
    from repro.kernels.bucketing import ROWGROUP_PAD
    from repro.kernels.digest_sig import digest_signature_rowgroup
    from repro.kernels.pattern_scan import find_pattern_mask_rowgroup

    rng = np.random.default_rng(21)
    width = 8192
    sizes = rng.integers(1, width, 22)
    payloads = [rng.integers(0, 4, int(n), np.uint8).tobytes()
                for n in sizes]
    mat = np.zeros((24, width + ROWGROUP_PAD), np.uint8)
    for i, p in enumerate(payloads):
        mat[i, :len(p)] = np.frombuffer(p, np.uint8)
    lengths = np.asarray(sizes, np.int64)
    pattern = b"\x01\x02\x03"
    trimmed = find_pattern_mask_rowgroup(mat, lengths, pattern)
    raw = find_pattern_mask_rowgroup(mat, lengths, pattern, trim=False)
    for i, mask in enumerate(find_pattern_mask_batch(payloads, pattern,
                                                     block=1024)):
        np.testing.assert_array_equal(trimmed[i, :len(mask)], mask)
        assert not trimmed[i, len(mask):].any()
        valid = max(len(mask) - len(pattern) + 1, 0)  # windows in bounds
        np.testing.assert_array_equal(raw[i, :valid], mask[:valid])
    d, s = digest_signature_rowgroup(mat, lengths)
    dr, sr = digest_signature_reference(payloads)
    np.testing.assert_array_equal(d, dr)
    np.testing.assert_array_equal(s, sr)


# --------------------------------------------------------------------------
# digest_sig (fused adler32 + n-gram signature sweep)
# --------------------------------------------------------------------------

def test_digest_sig_matches_two_pass_reference():
    rng = np.random.default_rng(7)
    payloads = [rng.integers(0, 256, size=int(s), dtype=np.uint8).tobytes()
                for s in rng.integers(0, 9000, 48)]
    payloads += [b"", b"a", b"abc", b"abcd", b"x" * 70_000]
    d, s = digest_signature_batch(payloads)
    dr, sr = digest_signature_reference(payloads)
    np.testing.assert_array_equal(d, dr)
    np.testing.assert_array_equal(s, sr)
    # digests really are zlib's
    for i, p in enumerate(payloads):
        assert int(d[i]) == (zlib.adler32(p) & 0xFFFFFFFF)


@pytest.mark.parametrize("bits,n,k", [(1024, 4, 2), (4096, 3, 1),
                                      (64, 5, 3), (8192, 2, 4)])
def test_digest_sig_geometry_sweep(bits, n, k):
    rng = np.random.default_rng(bits + n + k)
    payloads = [rng.integers(0, 256, size=int(sz), dtype=np.uint8).tobytes()
                for sz in rng.integers(0, 5000, 12)]
    d, s = digest_signature_batch(payloads, bits=bits, n=n, k=k)
    dr, sr = digest_signature_reference(payloads, bits=bits, n=n, k=k)
    np.testing.assert_array_equal(d, dr)
    np.testing.assert_array_equal(s, sr)


def test_digest_sig_empty_batch_and_bad_geometry():
    d, s = digest_signature_batch([])
    assert d.shape == (0,) and s.shape == (0, 64)
    with pytest.raises(ValueError):
        digest_signature_batch([b"xy"], bits=1000)   # not a power of two
    with pytest.raises(ValueError):
        digest_signature_batch([b"xy"], n=1)          # halo needs n >= 2


def test_digest_sig_signature_semantics():
    """Fused signatures keep the Bloom property queries rely on: every
    n-gram of a payload has all its bits set in the signature."""
    from repro.index.signature import pattern_bits

    payload = b"the quick brown fox jumps over the lazy dog" * 20
    _, sigs = digest_signature_batch([payload])
    required = pattern_bits(b"quick brown")
    assert ((sigs[0] & required) == required).all()


def test_verify_digests_bulk_mixed_algos():
    from repro.core.warc.checksum import block_digest, verify_digests_bulk
    payloads = _ragged_payloads(3, [10, 999, 2048, 0, 4097])
    headers = [block_digest(p, algo) for p, algo in zip(
        payloads, ["adler32", "sha1", "adler32", "crc32", "adler32"])]
    assert verify_digests_bulk(payloads, headers) == [True] * len(payloads)
    # corrupt one adler32 payload and one sha1 payload
    bad = list(payloads)
    bad[2] = bad[2][:-1] + bytes([bad[2][-1] ^ 0xFF])
    bad[1] = b"tampered" + bad[1]
    got = verify_digests_bulk(bad, headers)
    assert got == [True, False, False, True, True]
    # kernel-free fallback agrees
    assert verify_digests_bulk(bad, headers, use_kernel=False) == got


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

_SHAPES = [
    # B, H, Hkv, Sq, Sk, D
    (1, 4, 2, 128, 128, 64),
    (2, 8, 2, 256, 256, 64),
    (1, 4, 1, 128, 128, 128),   # MQA
    (1, 8, 8, 128, 512, 64),    # decode: cache longer than queries
    (1, 4, 4, 384, 384, 64),    # non-power-of-two block count
]


@pytest.mark.parametrize("shape", _SHAPES)
@pytest.mark.parametrize("causal", [True, False])
def test_flash_attention_shape_sweep(shape, causal):
    B, H, Hkv, Sq, Sk, D = shape
    ks = jax.random.split(jax.random.PRNGKey(B * Sq + Sk), 3)
    q = jax.random.normal(ks[0], (B, H, Sq, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, Hkv, Sk, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, Hkv, Sk, D), jnp.float32)
    out = flash_attention(q, k, v, causal=causal)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype,rtol", [(jnp.float32, 1e-4), (jnp.bfloat16, 2e-2)])
def test_flash_attention_dtype_sweep(dtype, rtol):
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64), jnp.float32).astype(dtype)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), jnp.float32).astype(dtype)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), jnp.float32).astype(dtype)
    out = flash_attention(q, k, v, causal=True)
    assert out.dtype == dtype
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=rtol, atol=rtol)


def test_flash_attention_block_size_invariance():
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 2, 512, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 512, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 512, 64), jnp.float32)
    a = flash_attention(q, k, v, block_q=128, block_k=128)
    b = flash_attention(q, k, v, block_q=256, block_k=64)
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-6)


def test_flash_attention_matches_tiny_fallback():
    # shapes not divisible by blocks route to the reference — same numbers
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (1, 2, 37, 64), jnp.float32)
    k = jax.random.normal(ks[1], (1, 2, 37, 64), jnp.float32)
    v = jax.random.normal(ks[2], (1, 2, 37, 64), jnp.float32)
    out = flash_attention(q, k, v, causal=True)
    ref = attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5, atol=1e-6)
