"""The attention-backend conformance matrix: one table of ``Cell``
dataclasses owning the whole {variant} x {kv_dtype} x {layout} x {family}
x {mode} space (ISSUE-5).

This table replaces the copy-pasted family lists the per-feature test
files (test_prefill / test_fused_decode / ...) used to re-derive: each
cell names exactly one datapath through the registry and carries its
documented tolerance against the fp32 full-sequence reference; cells the
architecture genuinely does not support are *skip entries with a reason
string*, so the matrix is auditable — a silent hole cannot exist.

Modes:

* ``forward``        — the full-sequence dispatch (impl="flash_jnp", the
                       training/eval path; quantized dtypes fake-quant).
* ``prefill_decode`` — chunked prefill + single-token decode through the
                       XLA serving backends (masked_xla / xla and their
                       paged gather twins) against real cache buffers.
* ``fused``          — the same serving split on the Pallas kernel family
                       (pallas / pallas_q, fused paged forms in-kernel).

Tolerance provenance (vs the same-variant fp32 one-pass full-sequence
reference, random N(0,1) operands, shapes as in test_conformance):

* fp32 / exact: pure float-accumulation-order noise, observed <= ~1e-6;
  documented 1e-4.
* int8: per-row symmetric codes, |elt err| <= amax/254 (numerics/quant.py
  contract); observed output drift ~1e-2; documented 5e-2.
* fp8 (e4m3fn): 3-bit mantissa, rel elt err <= 2^-4; observed ~4e-2;
  documented 1.5e-1.
* expmul: the paper's pow2 softmax weights carry up to ~0.49 relative
  weight error by design (numerics/log2exp.py), and the blocked kernels'
  L_hat rescale is tile-size dependent by construction — observed drift
  vs the same-variant one-pass reference up to ~0.43 when composed with
  the int8 codec (pow2 thresholds amplify near-tied maxima); documented
  4.5e-1 on top of the codec drift. The *tight* assertion for fused
  expmul cells is the same-tile pair check in test_conformance, not this
  reference tolerance.
"""
from __future__ import annotations

import dataclasses

VARIANTS = ("exact", "expmul")
KV_DTYPES = ("fp32", "int8", "fp8")
LAYOUTS = ("contiguous", "paged")
FAMILIES = ("mha", "gqa", "windowed", "mla")
MODES = ("forward", "prefill_decode", "fused")

# family -> attention-op shape parameters (dispatch level; "mla" is the
# expanded-latent shape the MLA layer hands the core: Dq != Dv, one head
# group)
FAMILY_SHAPES = {
    "mha": dict(H=4, Hkv=4, D=16, Dv=16, window=None),
    "gqa": dict(H=4, Hkv=2, D=16, Dv=16, window=None),
    "windowed": dict(H=4, Hkv=2, D=16, Dv=16, window=6),
    "mla": dict(H=4, Hkv=4, D=24, Dv=16, window=None),
}

# the prefix-cache oracle matrix (ISSUE-6): warm (cache-hit) temp-0 streams
# must be *bit-identical* to cold ones for every variant x kv_dtype the
# paged engine serves with caching on — expmul's chunk-grid-aligned resume
# cursor is exactly what makes this hold (DESIGN.md §11). fp8 rides the
# same code paths as int8 (codes + scale pools share the block tables), so
# the committed matrix covers {fp32, int8} and the bench covers the rest.
PREFIX_CACHE_CELLS = tuple(
    (variant, kv_dtype)
    for variant in VARIANTS
    for kv_dtype in ("fp32", "int8")
)

# model-level config families (arch, variant, prompt_len, chunk) shared by
# the end-to-end prefill/serving tests (previously copy-pasted there)
MODEL_FAMILIES = [
    ("qwen2-0.5b", "exact", 12, 5),        # GQA + qkv bias
    ("qwen2-0.5b", "expmul", 12, 5),       # the paper's variant
    ("minicpm3-4b", "exact", 12, 4),       # MLA latent cache, Dq != Dv
    ("recurrentgemma-2b", "exact", 48, 16),  # window=32 < prompt: cache rolls
]


@dataclasses.dataclass(frozen=True)
class Cell:
    variant: str
    kv_dtype: str
    layout: str
    family: str
    mode: str
    ref_tol: float = 0.0   # documented |out - fp32 full-sequence ref| bound
    skip: str = ""         # non-empty => skipped; the string is the reason

    @property
    def id(self) -> str:
        return (f"{self.variant}-{self.kv_dtype}-{self.layout}-"
                f"{self.family}-{self.mode}")


def _ref_tol(variant, kv_dtype) -> float:
    base = {"fp32": 1e-4, "int8": 5e-2, "fp8": 1.5e-1}[kv_dtype]
    return base + (4.5e-1 if variant == "expmul" else 0.0)


def _skip_reason(kv_dtype, layout, family, mode) -> str:
    if layout == "paged" and mode == "forward":
        return ("full-sequence dispatch has no paged calling convention "
                "(paging exists only for serving caches, DESIGN.md §7)")
    if family == "mla" and kv_dtype != "fp32":
        return ("MLA quantizes *latents* before expansion; the expanded-KV "
                "dispatch pins kv_dtype=fp32 so the registry never "
                "double-quantizes (DESIGN.md §8)")
    if family == "mla" and layout == "paged":
        return ("MLA's paged path dispatches one shared latent head with V "
                "inside K's rows (DESIGN.md §14, tests/test_moonlight.py); "
                "this matrix's MLA shape is per-head expanded K/V")
    if family == "windowed" and mode == "forward":
        # not a hole — forward windows are covered tightly by
        # test_kernel_flash / test_arch_smoke; the serving modes below are
        # what this matrix adds
        return ""
    return ""


CELLS = tuple(
    Cell(variant=variant, kv_dtype=kv_dtype, layout=layout, family=family,
         mode=mode, ref_tol=_ref_tol(variant, kv_dtype),
         skip=_skip_reason(kv_dtype, layout, family, mode))
    for variant in VARIANTS
    for kv_dtype in KV_DTYPES
    for layout in LAYOUTS
    for family in FAMILIES
    for mode in MODES
)
