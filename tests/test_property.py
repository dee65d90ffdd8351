"""System-level property tests (hypothesis): the invariants that matter.

* writer→parser round-trip: any record content/headers survive
  serialization + member compression + both parsers, bit-exact;
* recompression between any codec pair preserves every record;
* grouped MoE dispatch: output is invariant to the group count and equals
  the dense per-token reference under no-drop capacity.
"""
import io

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.warc import (
    FastWARCIterator,
    WARCIOArchiveIterator,
    WarcWriter,
    serialize_record,
)

_CODECS = ["none", "gzip", "lz4", "zstd"]

_hdr_name = st.text(
    alphabet=st.characters(min_codepoint=0x41, max_codepoint=0x5A),
    min_size=1, max_size=12).map(lambda s: "X-" + s)
_hdr_value = st.text(
    alphabet=st.characters(min_codepoint=0x20, max_codepoint=0x7E),
    min_size=0, max_size=40).map(str.strip)
_record = st.tuples(
    st.sampled_from(["response", "request", "metadata", "resource"]),
    st.binary(min_size=0, max_size=2048),
    st.dictionaries(_hdr_name, _hdr_value, max_size=4),
)


@given(st.lists(_record, min_size=1, max_size=6),
       st.sampled_from(_CODECS))
@settings(max_examples=60, deadline=None)
def test_writer_parser_roundtrip(records, compression):
    sink = io.BytesIO()
    w = WarcWriter(sink, compression)
    for rtype, content, headers in records:
        w.write_record(rtype, content, headers, digests=True)
    parsed = list(FastWARCIterator(sink.getvalue(), parse_http=False,
                                   verify_digests=True))
    assert len(parsed) == len(records)
    for rec, (rtype, content, headers) in zip(parsed, records):
        assert rec.record_type.name == rtype
        assert rec.content == content
        assert rec.verified_block_digest is True
        for name, value in headers.items():
            got = rec.headers.get(name)
            assert got is not None and got == value


@given(st.lists(_record, min_size=1, max_size=4))
@settings(max_examples=20, deadline=None)
def test_baseline_agrees_with_fast(records):
    sink = io.BytesIO()
    w = WarcWriter(sink, "gzip")
    for rtype, content, headers in records:
        w.write_record(rtype, content, headers)
    data = sink.getvalue()
    fast = list(FastWARCIterator(data, parse_http=False))
    base = list(WARCIOArchiveIterator(data))
    assert len(fast) == len(base) == len(records)
    for f, b in zip(fast, base):
        assert f.content == b.content
        assert f.record_type.name == b.rec_type


@given(st.lists(_record, min_size=1, max_size=6),
       st.sampled_from(["none", "gzip"]))
@settings(max_examples=40, deadline=None)
def test_zero_copy_parser_byte_identical_to_warcio_ref(records, compression):
    """ISSUE 4 property: the pooled-arena zero-copy parser is
    byte-identical to the WARCIO-faithful baseline on round-tripped
    archives — held records included (borrowed views must never alias
    recycled arena memory), and detach() must be value-preserving."""
    sink = io.BytesIO()
    w = WarcWriter(sink, compression)
    for rtype, content, headers in records:
        w.write_record(rtype, content, headers, digests=True)
    data = sink.getvalue()
    fast = list(FastWARCIterator(data, parse_http=False, zero_copy=True))
    base = list(WARCIOArchiveIterator(data, parse_http=False))
    assert len(fast) == len(base) == len(records)
    for f, b in zip(fast, base):
        borrowed = bytes(f.content_view())
        assert f.detach() is f
        assert f.content == b.content == borrowed
        assert f.record_type.name == b.rec_type
        assert f.record_id == b.record_id


@given(st.sampled_from(_CODECS), st.sampled_from(_CODECS))
@settings(max_examples=16, deadline=None)
def test_recompression_any_pair(src_codec, dst_codec):
    from repro.core.warc.writer import reserialize
    from repro.data.synth import CorpusSpec, generate_warc
    data = generate_warc(CorpusSpec(n_pages=5, seed=13), src_codec)
    sink = io.BytesIO()
    w = WarcWriter(sink, dst_codec)
    for rec in FastWARCIterator(data, parse_http=False):
        w.write_serialized(reserialize(rec))
    a = [(r.record_id, r.content) for r in FastWARCIterator(data)]
    b = [(r.record_id, r.content) for r in FastWARCIterator(sink.getvalue())]
    assert a == b


@given(st.integers(2, 6), st.integers(1, 3), st.integers(0, 10_000),
       st.sampled_from([1, 2, 4]))
@settings(max_examples=25, deadline=None)
def test_moe_group_invariance(log2_experts, top_k, seed, groups):
    from repro.models.moe import moe_apply, moe_init
    E = 2 ** log2_experts
    top_k = min(top_k, E)
    d, f, T = 16, 24, 32
    p = moe_init(jax.random.PRNGKey(seed), d, f, E, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (T, d))
    base, _ = moe_apply(p, x, top_k=top_k, capacity_factor=64.0, groups=1)
    out, _ = moe_apply(p, x, top_k=top_k, capacity_factor=64.0,
                       groups=groups)
    np.testing.assert_allclose(np.asarray(out), np.asarray(base),
                               rtol=2e-5, atol=2e-5)
