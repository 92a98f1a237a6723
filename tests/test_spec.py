"""Unit pins for the ScanSpec value type (DESIGN.md §14).

The spec is load-bearing in three ways — custom_vjp nondiff argument
(hashability), autotune cache key (canonical serialization), and test
enumerator (grid shape) — so its invariants are pinned directly rather
than inferred from the integration suites.
"""

import dataclasses

import jax.numpy as jnp
import pytest

from repro.kernels import autotune
from repro.kernels.spec import (BOUNDARIES, DIRECTIONS, IMPLS, ScanSpec,
                                canonical_key, enumerate_specs)

pytestmark = pytest.mark.kernels


def test_defaults_and_derived_views():
    sp = ScanSpec()
    assert sp.direction == "fwd" and sp.impl == "auto"
    # interpret=None: Mosaic when lowered for a TPU, the interpreter
    # elsewhere — no default ever forces the interpreter onto the chip.
    assert sp.boundary == "one_shot" and sp.interpret is None
    assert not sp.channel_shared and sp.channel_mode == "per_channel"
    assert sp.stream_bytes == 4
    assert ScanSpec(channels_per_weight=4).channel_mode == "shared"
    assert ScanSpec(stream_dtype="bfloat16").stream_bytes == 2


def test_frozen_and_hashable():
    sp = ScanSpec()
    with pytest.raises(dataclasses.FrozenInstanceError):
        sp.impl = "pallas"
    # Equal specs collapse to one dict/cache slot.
    assert {sp: 1, ScanSpec(): 2} == {ScanSpec(): 2}
    assert hash(ScanSpec(stream_dtype="float32")) == \
        hash(ScanSpec(stream_dtype=jnp.float32))


def test_dtype_spellings_normalise():
    """Any dtype spelling collapses to the canonical numpy name, so the
    cache key never splits on spelling."""
    for spelling in ("float32", jnp.float32, "f4", "<f4"):
        assert ScanSpec(stream_dtype=spelling).stream_dtype == "float32"
    assert ScanSpec(carry_dtype=jnp.bfloat16).carry_dtype == "bfloat16"


@pytest.mark.parametrize("bad", [
    dict(direction="diagonal"),
    dict(impl="cuda"),
    dict(boundary="wraparound"),
    dict(channels_per_weight=0),
    dict(channels_per_weight="4"),
    dict(row_tile=0),
    dict(row_tile=2.0),
    dict(pipeline_depth=3),
    dict(stream_dtype="notadtype"),
    dict(carry_dtype=object()),
])
def test_validation_rejects(bad):
    with pytest.raises(ValueError):
        ScanSpec(**bad)


def test_with_revalidates():
    sp = ScanSpec()
    assert sp.with_(impl="pallas").impl == "pallas"
    assert sp.with_(impl="pallas") is not sp
    with pytest.raises(ValueError):
        sp.with_(direction="sideways")


def test_adjoint():
    assert ScanSpec(direction="fwd").adjoint().direction == "bwd"
    pa = ScanSpec(direction="pair_fwd", carry_dtype="bfloat16").adjoint()
    assert pa.direction == "pair_bwd"
    assert pa.carry_dtype == "float32"          # adjoint carry is f32
    for d in ("bwd", "pair_bwd", "quad"):
        with pytest.raises(ValueError):
            ScanSpec(direction=d).adjoint()


def test_canonical_and_spec_id():
    sp = ScanSpec(direction="fwd", impl="pallas", channels_per_weight=3,
                  stream_dtype="bfloat16", carry_dtype="float32",
                  row_tile=8, pipeline_depth=2, boundary="chunk_resume")
    assert sp.canonical() == canonical_key(
        "fwd", "pallas", "bfloat16", "float32", True, "chunk_resume")
    assert sp.canonical() == \
        "fwd|pallas|bfloat16|carry-float32|cs1|bnd-chunk_resume"
    assert sp.spec_id() == sp.canonical() + "|cpw3|t8|d2|platform"
    assert sp.with_(interpret=True).spec_id().endswith("|interp")
    assert sp.with_(interpret=False).spec_id().endswith("|compiled")
    # tile/depth/interpret are launch mechanics, not cache policy.
    assert sp.with_(row_tile=None, pipeline_depth=None).canonical() == \
        sp.canonical()


def test_scan_key_encoding_ends_with_spec_canonical():
    """The tentpole contract: the schema-3 autotune cache key IS the
    device/shape legs + the spec's canonical serialization."""
    sp = ScanSpec(direction="pair_fwd", impl="multidir",
                  channels_per_weight=2, stream_dtype="bfloat16",
                  boundary="sp_block_local")
    key = autotune.ScanKey("cpu-interp", 64, 32, 8, sp.direction, sp.impl,
                           sp.stream_dtype, sp.carry_dtype,
                           sp.channel_shared, sp.boundary)
    assert key.encode().endswith(sp.canonical())
    assert key.encode() == "cpu-interp|h64|w32|c8|" + sp.canonical()


def test_enumerate_specs_shape():
    specs = enumerate_specs()
    assert len(specs) == 44 and len(set(specs)) == 44
    # Dispatch matrix: fwd→pallas/xla, pair_fwd→multidir/xla, quad→multidir.
    by_dir = {}
    for s in specs:
        by_dir.setdefault(s.direction, set()).add(s.impl)
    assert by_dir == {"fwd": {"pallas", "xla"},
                      "pair_fwd": {"multidir", "xla"},
                      "quad": {"multidir"}}
    # Boundary/cpw axes expand the grid multiplicatively.
    assert len(enumerate_specs(boundaries=BOUNDARIES)) == 3 * 44
    assert len(enumerate_specs(cpws=(1,))) == 22
    # Everything emitted is admissible by construction.
    for s in specs:
        assert s.direction in DIRECTIONS and s.impl in IMPLS
        assert s.boundary in BOUNDARIES
