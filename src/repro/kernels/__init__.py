"""Pallas TPU kernels for the compute hot-spots the paper optimizes.

The paper's speedups come from three per-byte passes (delimiter scanning,
checksumming) plus the downstream model compute this framework feeds:

* ``pattern_scan`` — multi-byte delimiter search over uint8 buffers: the
  TPU-VPU adaptation of FastWARC's SIMD ``memchr``/``strstr`` bulk scans.
* ``adler32``     — the rolling checksum as blocked reductions (CRC-32's
  bit-feedback loop does not transfer to the VPU; see DESIGN.md §4).
* ``digest_sig``  — fused Adler-32 + n-gram-signature sweep: both CDX
  byte columns from one batched pass (DESIGN.md §9).
* ``flash_attention`` — blocked GQA attention with online softmax: the
  training/serving hot-spot of the LM architectures this pipeline feeds.

Each subpackage: ``<name>.py`` (pl.pallas_call + BlockSpec), ``ops.py``
(jit'd public wrapper), ``ref.py`` (pure-jnp oracle used by the tests).
The byte kernels compile for the TPU and run in the Pallas interpreter on
the CPU backend; :func:`interpret_mode` makes that choice in one place.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

# <checkout>/.jax_cache: fixed, because the directory is part of what a
# later run has to find again (never a temporary, pid- or time-derived path)
_CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def interpret_mode() -> bool:
    """Whether the byte kernels run in the Pallas interpreter.

    Derived from JAX's default backend: ``True`` on ``cpu`` (tests and
    rehearsals), ``False`` on ``tpu``. Any other backend raises — the
    kernels are written for the TPU's Mosaic compiler, and a guess would
    either time the interpreter on an accelerator or hand Mosaic to a
    backend that cannot compile it.
    """
    import jax

    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"no Pallas byte-kernel path for the {platform!r} backend "
        "(supported: tpu, and cpu through the interpreter)")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    ``JAX_COMPILATION_CACHE_DIR``, where set, places the cache and no
    other directory is set; otherwise it lives in ``.jax_cache`` at the
    root of the checkout. Every program is cached, however quickly it
    compiled: a cold run compiles one kernel per width bucket, each well
    under JAX's default one-second threshold. Entry points call this
    first; importing the library never does. Before JAX is imported the
    settings go through the environment, so calling this does not
    import JAX (pool workers forked before that stay JAX-free).
    """
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(_CHECKOUT_CACHE))
    os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = "0"
    path = os.environ["JAX_COMPILATION_CACHE_DIR"]
    jax = sys.modules.get("jax")
    if jax is not None:  # imported already: its config read the old values
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path
