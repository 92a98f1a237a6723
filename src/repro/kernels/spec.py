"""Declarative scan configuration: the ``ScanSpec`` (DESIGN.md §14).

GSPN-2's pitch is one kernel structure serving many propagation variants,
yet before this module every launch path hand-threaded the same knobs
(direction, channel mode, dtype policy, row_tile, pipeline_depth,
boundary behaviour) as loose keyword arguments — adding one knob meant
touching five call sites.  ``ScanSpec`` is the single frozen, hashable
value that carries ALL of them:

* every launch site (``ops`` dispatch, ``gspn_scan`` fwd/bwd,
  ``gspn_multidir`` pair/quad, the sp block-local scan, the serve
  chunked-prefill path) constructs ONE spec and hands it down;
* the autotuner keys its persistent cache on the spec's canonical
  serialization (:func:`canonical_key` — cache schema 3);
* the test suite enumerates the full admissible spec space
  (:func:`enumerate_specs`) and runs every emitted spec fwd+grad against
  the reference, so a new propagation variant is a spec plus an
  automatic conformance entry, not a fifth kernel fork.

This module is a LEAF: it imports nothing from the rest of the kernel
stack so every layer (kernels, ops, sp, core, autotune, benchmarks) can
import it without cycles.
"""

from __future__ import annotations

import dataclasses
import itertools

import jax.numpy as jnp

# The admissible vocabulary.  ``direction`` names the fused-kernel entry
# (not the spatial orientation tb/bt/lr/rl — orientation is handled by
# core/gspn canonicalisation and always lowers to one of these).
DIRECTIONS = ("fwd", "bwd", "pair_fwd", "pair_bwd", "quad")

# How a scan segment relates to state outside itself (DESIGN.md §14):
#   one_shot        — the whole sequence in one launch, zero initial carry;
#   chunk_resume    — serve chunked prefill: the carry enters as a
#                     synthetic resumed row (core/gspn.gspn_seq_prefill_chunk);
#   sp_block_local  — sequence-parallel block-local scan: zero initial
#                     carry per block, boundaries exchanged by collectives
#                     (parallel/gspn_sp).
BOUNDARIES = ("one_shot", "chunk_resume", "sp_block_local")

# Kernel-selection leg.  "auto" resolves per backend (ops._resolve_impl);
# "sp" routes to the sequence-parallel wrapper; the rest name concrete
# implementations.
IMPLS = ("auto", "pallas", "multidir", "xla", "per_step", "sp")

_ADJOINT = {"fwd": "bwd", "pair_fwd": "pair_bwd"}


def canonical_key(direction: str, impl: str, stream_dtype: str,
                  carry_dtype: str, channel_shared: bool,
                  boundary: str) -> str:
    """The policy leg of the schema-3 autotune cache key.  Shared between
    :meth:`ScanSpec.canonical` and ``autotune.ScanKey.encode`` so "keyed
    on the spec's canonical serialization" is literally true: a ScanKey's
    encoding ends with the owning spec's canonical string."""
    return (f"{direction}|{impl}|{stream_dtype}|carry-{carry_dtype}"
            f"|cs{int(channel_shared)}|bnd-{boundary}")


def _dtype_name(dtype) -> str:
    try:
        return str(jnp.dtype(dtype))
    except TypeError as exc:
        raise ValueError(f"unknown dtype {dtype!r}") from exc


@dataclasses.dataclass(frozen=True)
class ScanSpec:
    """Everything one fused-scan launch needs to know about itself.

    Frozen and built only from hashables so a spec can be a custom_vjp
    nondiff argument, a dict key, and a cache key.  Shape-derived fields
    (``channels_per_weight``, ``stream_dtype``) are refined by the
    dispatch layer from the operands; the caller-supplied values act as
    defaults.
    """

    direction: str = "fwd"             # DIRECTIONS
    impl: str = "auto"                 # IMPLS
    channels_per_weight: int = 1       # compact channel mode: G = G_w·cpw
    stream_dtype: str = "float32"      # streamed operand tiles
    carry_dtype: str = "float32"       # VMEM carry (f32 under the policy)
    row_tile: int | None = None        # None = ask the autotuner
    pipeline_depth: int | None = None  # None = tuner/heuristic; 1 | 2
    boundary: str = "one_shot"         # BOUNDARIES
    # Pallas interpret mode: None follows the platform being lowered for
    # (Mosaic on a TPU, the interpreter elsewhere); a bool forces it.
    interpret: bool | None = None

    def __post_init__(self):
        if self.direction not in DIRECTIONS:
            raise ValueError(f"unknown direction {self.direction!r}; "
                             f"expected one of {DIRECTIONS}")
        if self.impl not in IMPLS:
            raise ValueError(f"unknown impl {self.impl!r}; "
                             f"expected one of {IMPLS}")
        if self.boundary not in BOUNDARIES:
            raise ValueError(f"unknown boundary {self.boundary!r}; "
                             f"expected one of {BOUNDARIES}")
        if not isinstance(self.channels_per_weight, int) \
                or self.channels_per_weight < 1:
            raise ValueError(f"channels_per_weight must be a positive int, "
                             f"got {self.channels_per_weight!r}")
        if self.row_tile is not None and (
                not isinstance(self.row_tile, int) or self.row_tile < 1):
            raise ValueError(f"row_tile must be a positive int or None, "
                             f"got {self.row_tile!r}")
        if self.pipeline_depth not in (None, 1, 2):
            raise ValueError(f"pipeline_depth must be None, 1 or 2, "
                             f"got {self.pipeline_depth!r}")
        # Normalise dtype spellings ("f32", np.float32, jnp.bfloat16) to
        # the canonical numpy name so spec equality/hashing — and through
        # them the cache key — never splits on spelling.
        object.__setattr__(self, "stream_dtype",
                           _dtype_name(self.stream_dtype))
        object.__setattr__(self, "carry_dtype",
                           _dtype_name(self.carry_dtype))

    # -- derived views -----------------------------------------------------

    @property
    def channel_shared(self) -> bool:
        """Compact channel propagation active (weights span cpw planes)."""
        return self.channels_per_weight > 1

    @property
    def channel_mode(self) -> str:
        return "shared" if self.channel_shared else "per_channel"

    @property
    def stream_bytes(self) -> int:
        return jnp.dtype(self.stream_dtype).itemsize

    # -- serialization / derivation ---------------------------------------

    def canonical(self) -> str:
        """Canonical policy serialization — the trailing leg of the
        schema-3 autotune cache key (see :func:`canonical_key`)."""
        return canonical_key(self.direction, self.impl, self.stream_dtype,
                             self.carry_dtype, self.channel_shared,
                             self.boundary)

    def spec_id(self) -> str:
        """Full human-readable identity (test ids, trace annotations)."""
        t = self.row_tile if self.row_tile is not None else "auto"
        d = self.pipeline_depth if self.pipeline_depth is not None else "auto"
        mode = {None: "platform", True: "interp",
                False: "compiled"}[self.interpret]
        return (f"{self.canonical()}|cpw{self.channels_per_weight}"
                f"|t{t}|d{d}|{mode}")

    def with_(self, **changes) -> "ScanSpec":
        """``dataclasses.replace`` with re-validation (frozen update)."""
        return dataclasses.replace(self, **changes)

    def adjoint(self) -> "ScanSpec":
        """The spec of this launch's backward pass: the adjoint direction
        with the always-f32 adjoint carry (DESIGN.md §10).  Only forward
        directions have a fused adjoint kernel."""
        if self.direction not in _ADJOINT:
            raise ValueError(f"no fused adjoint for direction "
                             f"{self.direction!r}")
        return self.with_(direction=_ADJOINT[self.direction],
                          carry_dtype="float32")


def enumerate_specs(*, boundaries=("one_shot",),
                    cpws=(1, 3)) -> list[ScanSpec]:
    """The FULL admissible forward spec grid — the single source of truth
    the conformance sweep runs against (every emitted spec must pass
    fwd+grad vs the reference; tests/test_conformance.py).

    Shape of the grid:

    * direction × impl follows the dispatch matrix (fwd: pallas/xla,
      pair_fwd: multidir/xla, quad: multidir-only);
    * stream dtype f32 and bf16; carry is f32 (the policy default) plus
      the aggressive stream-width carry for narrow streams;
    * channel mode per-channel (cpw=1) and compact (cpw>1);
    * pipeline depth 1 and 2 for the fused kernels (the kernels accept
      depth 2 at any dtype — which keys the tuner stages is admission
      policy, ``autotune.depth_admissible``, not capability), None for
      xla (no pipeline);
    * every requested boundary behaviour (numerics are boundary-label
      invariant; the label keys the cache and the routing).

    Backward/adjoint specs are not enumerated separately: every grid
    entry runs fwd AND grad, which exercises the adjoint kernels through
    ``ScanSpec.adjoint``.
    """
    impls_for = {"fwd": ("pallas", "xla"),
                 "pair_fwd": ("multidir", "xla"),
                 "quad": ("multidir",)}
    out: list[ScanSpec] = []
    for direction, boundary, cpw in itertools.product(
            impls_for, boundaries, cpws):
        for impl in impls_for[direction]:
            for stream in ("float32", "bfloat16"):
                if impl == "xla":
                    # XLA reference path: no VMEM carry, no pipeline —
                    # those legs collapse to the policy default.
                    out.append(ScanSpec(
                        direction=direction, impl=impl,
                        channels_per_weight=cpw, stream_dtype=stream,
                        boundary=boundary))
                    continue
                carries = ("float32",) if stream == "float32" \
                    else ("float32", "bfloat16")
                for carry, depth in itertools.product(carries, (1, 2)):
                    out.append(ScanSpec(
                        direction=direction, impl=impl,
                        channels_per_weight=cpw, stream_dtype=stream,
                        carry_dtype=carry, pipeline_depth=depth,
                        boundary=boundary))
    return out
