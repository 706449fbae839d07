"""Unified attention backend registry (DESIGN.md §3).

One ``AttentionSpec`` describes *how* attention is computed — implementation,
arithmetic variant (exact vs the paper's ExpMul), block sizes, local window —
independently of *where* it is called from: full-sequence train/forward,
chunked prefill, or single-token KV-cache decode. The three call sites
(``core/attention.py``, ``layers/attention_layer.py``, ``layers/mla.py``)
all route through the dispatch tables below instead of carrying their own
string-dispatch, so config-driven impl/variant selection behaves identically
in train, serve, and bench.

Five tables, one per calling convention:

  full sequence   fn(q, k, v, *, spec, causal, scale)       -> (B, H, Sq, Dv)
  chunked prefill fn(q, k_cache, v_cache, k_chunk, v_chunk,
                     *, spec, scale, lengths, n_valid,
                     rolling)                               -> (B, H, C, Dv)
  decode          fn(q, k_cache, v_cache, lengths,
                     *, spec, scale)                        -> (B, H, Dv)
  paged prefill   fn(q, k_chunk, v_chunk, k_pool, v_pool,
                     rows, *, spec, scale, q_positions,
                     chunk_valid, lengths)                  -> (B, H, C, Dv)
  paged decode    fn(q, k_pool, v_pool, rows, lengths,
                     *, spec, scale)                        -> (B, H, Dv)

The chunked-prefill convention (DESIGN.md §10) passes the resident cache
and the chunk's fresh KV as *separate* operands plus two per-sequence
scalars (``lengths`` tokens resident, ``n_valid`` valid chunk tokens;
``rolling`` marks windowed rolling-buffer caches): positions and validity
are derivable from those, so fused backends mask in-kernel and never
materialize the [cache ++ chunk] concatenation, while the masked-XLA
backend rebuilds the positional tensors itself.

The paged conventions (DESIGN.md §7) take KV as a flat physical token pool
``(pool_tokens, Hkv, ·)`` plus ``rows (B, L)`` — per-sequence physical row
indices in logical position order, derived from the block table by
``repro.kernels.paged.slot_rows`` — instead of per-slot contiguous caches.
Position ``j`` of sequence ``b`` lives at ``rows[b, j]``; masking stays
purely positional (``j < lengths[b]``, window by ``lengths - j``). Both
paged dispatchers additionally forward the raw ``block_tables (B,
max_blocks)`` and ``page_size`` when the caller has them: fused kernels
(the ``pallas`` paged decode, DESIGN.md §9) resolve pool rows *inside* the
kernel from the table and never touch ``rows``; gather-style backends
ignore them.

Built-in implementations live in ``repro.core.attention`` and register
themselves on import; new backends (e.g. a Pallas prefill kernel) register
under a new name and become selectable purely through the model config.

A registration may declare itself a **fallback** (``register_*(name,
fallback_of="other")``) when the name routes to another implementation's
math rather than a dedicated kernel. Since the Pallas prefill kernels
landed (DESIGN.md §10) every built-in registration is a real
implementation — no table carries a ``fallback_of`` declaration — but the
mechanism stays so a future partial backend can never be silent.
``resolved_backends(spec)`` reports, per dispatch table, what a spec
actually runs (declared fallbacks and the CPU interpret-mode caveat for
Pallas kernels); ``ServeEngine`` logs the non-obvious rows once at
startup so a requested impl can never silently mean something else.

``AttentionSpec.kv_dtype`` adds a quantized-KV axis to every table
(DESIGN.md §8): when it is "int8" or "fp8" the resolvers return the
``<base>_q`` entry — registered by ``repro.kernels.kvquant`` — whose
cache-side K/V operands are ``numerics.quant.QuantKV`` (codes + per-row
float32 scales) and which dequantizes fused into the attention inner loop,
so full-precision K/V never round-trips through cache storage.
"""
from __future__ import annotations

import dataclasses

import jax


@dataclasses.dataclass(frozen=True)
class AttentionSpec:
    """Everything attention dispatch needs beyond the operands.

    ``impl`` names the full-sequence kernel; ``decode_impl`` and
    ``prefill_impl`` default (None) to the natural companion of ``impl``
    so a config only has to pick one backend family.
    """

    impl: str = "flash_jnp"          # ref | flash_jnp | pallas | ...
    decode_impl: str | None = None   # xla | pallas | ...
    prefill_impl: str | None = None  # masked_xla | pallas | ...
    paged_impl: str | None = None    # gather_xla | ... (prefill and decode)
    variant: str = "exact"           # exact | expmul
    use_ste: bool = False            # straight-through grads for expmul
    window: int | None = None        # local attention span
    kv_dtype: str = "fp32"           # fp32 | int8 | fp8 (KV-cache storage)
    block_q: int = 128
    block_k: int = 512
    decode_block_k: int = 256
    q_chunks: int = 4                # causal block skipping (flash_jnp)
    remat: bool = True

    def quantized(self) -> bool:
        """True when KV is stored quantized (DESIGN.md §8).

        Quantized specs resolve to the ``<base>_q`` entry of each table:
        the cache-side K/V operands arrive as ``numerics.quant.QuantKV``
        (codes + per-row scales) and the impl dequantizes fused into its
        inner loop; the full-sequence ``_q`` impls fake-quant fresh K/V so
        train/forward numerics match a cache round-trip exactly.
        """
        return self.kv_dtype != "fp32"

    def _q(self, name: str) -> str:
        return name + "_q" if self.quantized() else name

    def resolved_impl(self) -> str:
        return self._q(self.impl)

    def resolved_decode_impl(self) -> str:
        if self.decode_impl is not None:
            return self._q(self.decode_impl)
        return self._q("pallas" if self.impl == "pallas" else "xla")

    def resolved_prefill_impl(self) -> str:
        if self.prefill_impl is not None:
            return self._q(self.prefill_impl)
        # like decode: one ``impl="pallas"`` knob selects the whole family,
        # and since DESIGN.md §10 the pallas prefill entry is a real fused
        # kernel, not a fallback
        return self._q("pallas" if self.impl == "pallas" else "masked_xla")

    def resolved_paged_impl(self) -> str:
        if self.paged_impl is not None:
            return self._q(self.paged_impl)
        # like decode: one ``impl="pallas"`` knob selects the whole family
        # (fused paged decode kernel + its documented prefill fallback)
        return self._q("pallas" if self.impl == "pallas" else "gather_xla")

    @classmethod
    def from_config(cls, cfg, *, window=None, variant=None,
                    use_ste=False, kv_dtype=None) -> "AttentionSpec":
        """Build a spec from a ModelConfig (the single cfg->kernel mapping).

        ``kv_dtype`` overrides ``cfg.kv_dtype`` — layers that manage their
        own quantization outside the dispatch (MLA quantizes *latents*
        before expansion) pass ``kv_dtype="fp32"`` so the core never
        double-quantizes the expanded K/V.
        """
        return cls(
            impl=cfg.attention_impl,
            decode_impl=cfg.attention_decode_impl,
            prefill_impl=cfg.attention_prefill_impl,
            paged_impl=cfg.attention_paged_impl,
            variant=variant if variant is not None else cfg.attention_variant,
            use_ste=use_ste,
            window=window,
            kv_dtype=kv_dtype if kv_dtype is not None else cfg.kv_dtype,
            block_q=cfg.attention_block_q,
            block_k=cfg.attention_block_k,
            q_chunks=cfg.attention_q_chunks,
            remat=cfg.remat,
        )

    def replace(self, **kw) -> "AttentionSpec":
        return dataclasses.replace(self, **kw)


_ATTENTION_IMPLS: dict[str, object] = {}
_PREFILL_IMPLS: dict[str, object] = {}
_DECODE_IMPLS: dict[str, object] = {}
_PAGED_PREFILL_IMPLS: dict[str, object] = {}
_PAGED_DECODE_IMPLS: dict[str, object] = {}

# observability hook (DESIGN.md §12): when set, every dispatch_* call
# reports (kind, spec, operand geometry) before running. The hook lives
# here — the kernels layer exposes the slot, ``repro.serve.metrics``
# installs into it — so kernels never import the serving stack. Dispatch
# runs at Python call time: 1:1 with attention calls for eager callers,
# once per trace under jax.jit (the engine's executed-cost ledger covers
# per-step attribution). ``None`` (the default) costs one predicate check.
_DISPATCH_SINK = None


def set_dispatch_sink(sink) -> None:
    """Install (or with ``None`` remove) the global dispatch observer —
    see ``repro.serve.metrics.install_dispatch_counters``."""
    global _DISPATCH_SINK
    _DISPATCH_SINK = sink


def _shape(x):
    """Static operand shape: QuantKV operands report their codes' shape
    (same token/head geometry as the raw array they replace)."""
    return getattr(x, "codes", x).shape

# (table kind, registered name) -> name of the implementation whose math the
# entry actually runs. Populated by ``register_*(..., fallback_of=...)`` and
# surfaced by ``resolved_backends`` — a requested backend never silently
# means something else (ISSUE-4 satellite).
_FALLBACK_NOTES: dict[tuple[str, str], str] = {}


def _make_register(table, kind):
    def register(name: str, *, fallback_of: str | None = None):
        def deco(fn):
            table[name] = fn
            if fallback_of is not None:
                _FALLBACK_NOTES[(kind, name)] = fallback_of
            return fn
        return deco
    return register


register_attention = _make_register(_ATTENTION_IMPLS, "full-sequence")
register_prefill = _make_register(_PREFILL_IMPLS, "prefill")
register_decode = _make_register(_DECODE_IMPLS, "decode")
register_paged_prefill = _make_register(_PAGED_PREFILL_IMPLS, "paged prefill")
register_paged_decode = _make_register(_PAGED_DECODE_IMPLS, "paged decode")


def resolved_backends(spec: AttentionSpec, *, paged: bool = False) -> list[dict]:
    """What this spec actually runs, per dispatch table.

    Returns one dict per table: ``{"kind", "requested", "resolved",
    "fallback", "note"}`` where ``resolved`` differs from ``requested``
    when the registered entry is a declared fallback onto another
    implementation's math, and ``note`` carries the CPU interpret-mode
    caveat for Pallas kernels. Serving engines log the non-trivial rows
    once at startup (DESIGN.md §9).
    """
    _lookup(_ATTENTION_IMPLS, "ref", "full-sequence")  # force registration
    kinds = [
        ("full-sequence", spec.resolved_impl()),
        ("prefill", spec.resolved_prefill_impl()),
        ("decode", spec.resolved_decode_impl()),
    ]
    if paged:
        kinds += [
            ("paged prefill", spec.resolved_paged_impl()),
            ("paged decode", spec.resolved_paged_impl()),
        ]
    on_cpu = jax.default_backend() == "cpu"
    out = []
    for kind, name in kinds:
        resolved = _FALLBACK_NOTES.get((kind, name), name)
        note = ""
        if on_cpu and "pallas" in resolved:
            note = "interpret mode (CPU has no Pallas TPU lowering)"
        out.append({
            "kind": kind,
            "requested": name,
            "resolved": resolved,
            "fallback": resolved != name,
            "note": note,
        })
    return out


def _lookup(table, name, kind):
    if name not in table:
        # built-ins register on import of the core module (and the ``_q``
        # quantized variants on import of kernels.kvquant); importing
        # lazily here breaks the registry <-> core circular dependency
        import repro.core.attention  # noqa: F401
        import repro.kernels.kvquant  # noqa: F401
    try:
        return table[name]
    except KeyError:
        raise ValueError(
            f"unknown {kind} attention impl {name!r}; "
            f"registered: {sorted(table)}"
        ) from None


def attention_impls() -> tuple[str, ...]:
    _lookup(_ATTENTION_IMPLS, "ref", "full-sequence")
    return tuple(sorted(_ATTENTION_IMPLS))


def dispatch_attention(spec: AttentionSpec, q, k, v, *, causal=True,
                       scale=None):
    """Full-sequence attention. q: (B,H,Sq,D); k/v: (B,Hkv,Sk,·)."""
    fn = _lookup(_ATTENTION_IMPLS, spec.resolved_impl(), "full-sequence")
    if _DISPATCH_SINK is not None:
        qs, ks, vs = _shape(q), _shape(k), _shape(v)
        _DISPATCH_SINK("full", spec, batch=qs[0], heads=qs[1],
                       heads_kv=ks[1], d_qk=ks[-1], d_v=vs[-1],
                       kv_tokens=ks[2], q_tokens=qs[2])
    return fn(q, k, v, spec=spec, causal=causal, scale=scale)


def dispatch_prefill(spec: AttentionSpec, q, k_cache, v_cache, k_chunk,
                     v_chunk, *, lengths, n_valid, scale=None,
                     rolling=False):
    """Chunked-prefill attention: chunk queries over [cache ++ chunk].

    q: (B, H, C, D) chunk queries; k_cache/v_cache: (B, Hkv, S, ·) the
    resident cache buffers (raw arrays, or ``QuantKV`` codes + scales for
    quantized specs); k_chunk/v_chunk: (B, Hkv, C, ·) this chunk's fresh
    KV (same representation); lengths: (B,) tokens already resident;
    n_valid: (B,) valid chunk tokens (idle rows pass 0 and produce
    garbage-but-finite outputs).

    Positions are implied: chunk token i sits at ``lengths + i``; cache
    slot j holds position j (``rolling=False``) or the rolling-buffer
    position ``last - ((last - j) % S)`` (``rolling=True`` — windowed
    layers). Query i sees KV j iff position_j <= position_i (and within
    ``spec.window`` when set). Backends either rebuild the positional
    tensors (masked_xla) or mask in-kernel without materializing the
    concatenation (pallas — DESIGN.md §10).
    """
    fn = _lookup(_PREFILL_IMPLS, spec.resolved_prefill_impl(), "prefill")
    if _DISPATCH_SINK is not None:
        qs, ks, vs = _shape(q), _shape(k_cache), _shape(v_cache)
        _DISPATCH_SINK("prefill", spec, batch=qs[0], heads=qs[1],
                       heads_kv=ks[1], d_qk=ks[-1], d_v=vs[-1],
                       kv_tokens=ks[2], q_tokens=qs[2])
    return fn(q, k_cache, v_cache, k_chunk, v_chunk, spec=spec, scale=scale,
              lengths=lengths, n_valid=n_valid, rolling=rolling)


def dispatch_decode(spec: AttentionSpec, q, k_cache, v_cache, lengths, *,
                    scale=None):
    """Single-token decode. q: (B,H,D); caches: (B,Hkv,S,·); lengths: (B,)."""
    fn = _lookup(_DECODE_IMPLS, spec.resolved_decode_impl(), "decode")
    if _DISPATCH_SINK is not None:
        qs, ks, vs = _shape(q), _shape(k_cache), _shape(v_cache)
        _DISPATCH_SINK("decode", spec, batch=qs[0], heads=qs[1],
                       heads_kv=ks[1], d_qk=ks[-1], d_v=vs[-1],
                       kv_tokens=ks[2], q_tokens=1)
    return fn(q, k_cache, v_cache, lengths, spec=spec, scale=scale)


def dispatch_paged_prefill(spec: AttentionSpec, q, k_chunk, v_chunk, k_pool,
                           v_pool, rows, *, q_positions, chunk_valid, lengths,
                           scale=None, block_tables=None, page_size=0):
    """Chunked prefill against a paged KV pool (DESIGN.md §7).

    q: (B, H, C, D) chunk queries; k_chunk/v_chunk: (B, Hkv, C, ·) this
    chunk's fresh KV (not yet in the pool); k_pool/v_pool: (pool_tokens,
    Hkv, ·) flat physical pools; rows: (B, L) physical rows of logical
    positions 0..L-1 (sentinel rows read as zero and are masked);
    q_positions: (B, C) absolute chunk positions; chunk_valid: (B, C) bool;
    lengths: (B,) tokens already resident. The implementation gathers the
    history through ``rows`` and masks positionally exactly like the
    contiguous prefill path.
    """
    fn = _lookup(_PAGED_PREFILL_IMPLS, spec.resolved_paged_impl(),
                 "paged prefill")
    if _DISPATCH_SINK is not None:
        qs, ks, vs = _shape(q), _shape(k_pool), _shape(v_pool)
        _DISPATCH_SINK("paged_prefill", spec, batch=qs[0], heads=qs[1],
                       heads_kv=ks[1], d_qk=ks[-1], d_v=vs[-1],
                       kv_tokens=_shape(rows)[1], q_tokens=qs[2],
                       page_size=page_size)
    return fn(q, k_chunk, v_chunk, k_pool, v_pool, rows, spec=spec,
              scale=scale, q_positions=q_positions, chunk_valid=chunk_valid,
              lengths=lengths, block_tables=block_tables,
              page_size=page_size)


def dispatch_paged_decode(spec: AttentionSpec, q, k_pool, v_pool, rows,
                          lengths, *, scale=None, block_tables=None,
                          page_size=0):
    """Single-token decode against a paged KV pool.

    q: (B, H, D); pools: (pool_tokens, Hkv, ·); rows: (B, L) physical rows
    in logical position order (the current token's KV must already be
    written); lengths: (B,) valid entries *including* the current token.
    ``spec.window`` masks positions below ``lengths - window``.
    ``block_tables``/``page_size``, when provided, let fused backends
    resolve pool rows inside the kernel instead of gathering via ``rows``.
    """
    fn = _lookup(_PAGED_DECODE_IMPLS, spec.resolved_paged_impl(),
                 "paged decode")
    if _DISPATCH_SINK is not None:
        qs, ks, vs = _shape(q), _shape(k_pool), _shape(v_pool)
        _DISPATCH_SINK("paged_decode", spec, batch=qs[0], heads=qs[1],
                       heads_kv=ks[1], d_qk=ks[-1], d_v=vs[-1],
                       kv_tokens=_shape(rows)[1], q_tokens=1,
                       page_size=page_size)
    return fn(q, k_pool, v_pool, rows, lengths, spec=spec, scale=scale,
              block_tables=block_tables, page_size=page_size)
