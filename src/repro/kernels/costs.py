"""Analytic datapath cost model for attention dispatches (DESIGN.md §12).

One place that prices what a dispatched attention call is *designed* to
move and compute — the IO-aware cost signal behind every fused-vs-gather
claim in this repo (FlashAttention's core argument is counting the bytes
the kernel actually touches; on the CPU software proxy wall-clock ranks
backends wrongly, so analytic bytes are the tracked metric).

These helpers started life inside ``benchmarks/decode_microbench.py`` /
``benchmarks/prefill_microbench.py``, which still report them
(``analytic_bytes_per_ctx_token`` / ``analytic_bytes_per_chunk_token``
keep their exact signatures and semantics — BENCH_decode.json /
BENCH_prefill.json numbers are unchanged). ``impl_path`` also labels the
dispatch counters of ``serve/metrics.py``.

Cost conventions (documented per helper): q/output traffic is excluded
(identical across paths), gather datapaths pay a write + read of the
materialized fp32 copy, paged layouts amortize the int32 block-table
read, quantized dtypes add the per-row float32 scale reads.
"""
from __future__ import annotations

SCALE_BYTES = 4   # per-row float32 scale (numerics/quant.py contract)
F32 = 4
TABLE_BYTES = 4   # int32 block-table entry, amortized over page_size tokens


def kv_code_bytes(kv_dtype: str) -> int:
    """Bytes per stored KV element: 1 for int8/fp8 codes, 4 for fp32.

    Kept jax-free (the numerics.quant twin consults jnp dtypes) so this
    module stays importable from anywhere, metrics included.
    """
    return F32 if kv_dtype == "fp32" else 1


def impl_path(impl: str) -> str:
    """Map a resolved registry impl name onto the cost model's two
    datapaths: ``"fused"`` (Pallas kernels — in-kernel block tables,
    in-register dequant, no materialized copy) vs ``"gather"``
    (everything else: gather/concat/dequant into a contiguous fp32 copy
    first; the contiguous-fp32 ``xla``/``masked_xla`` forms read in
    place, which the helpers already price as zero copy overhead)."""
    return "fused" if "pallas" in impl and "gather" not in impl else "gather"


def analytic_bytes_per_ctx_token(layout, kv_dtype, path, *, Hkv, D, Dv,
                                 page_size):
    """Designed HBM bytes touched per context token for one decode step.

    Counted per logical token of resident history, summed over the K and V
    rows of all ``Hkv`` heads:

      * cache read — what the attention math must load: codes (1 B/elt) +
        scale rows for quantized dtypes, 4 B/elt for fp32.
      * gather overhead — the gather datapaths materialize a contiguous
        fp32 copy of the (dequantized) history before attending, paying a
        full write + read of that copy on top of the cache read. The
        contiguous-fp32 gather ("xla") reads the cache in place (masked
        one-pass softmax, no copy), so its overhead is zero — fused vs
        gather only diverges where a copy exists (every paged cell and,
        in time if not bytes, the dequant cells).
      * paged adds the block-table read, amortized per token.

    q/o traffic is context-independent and excluded (identical across
    paths).
    """
    elt = kv_code_bytes(kv_dtype)
    cache_read = Hkv * (D + Dv) * elt
    if kv_dtype != "fp32":
        cache_read += Hkv * 2 * SCALE_BYTES
    copy = 2 * Hkv * (D + Dv) * F32  # write + read of the fp32 copy
    b = cache_read
    if layout == "paged":
        b += TABLE_BYTES / page_size
        if path == "gather":
            b += copy
    elif path == "gather" and kv_dtype != "fp32":
        # contiguous quantized gather: dequantized fp32 copy of the cache
        b += copy
    return b


def analytic_bytes_per_chunk_token(layout, kv_dtype, path, *, Hkv, D, Dv,
                                   ctx, chunk, page_size):
    """Designed HBM bytes touched per *chunk token* for one prefill step.

    A chunk of ``chunk`` fresh tokens attends over ``ctx`` resident
    history tokens plus itself; per KV head a token row costs
    ``(D + Dv) * elt`` bytes (+ 2 scale rows when quantized):

      * history read — what the attention math must load once per chunk:
        codes (1 B/elt) + scale rows for quantized dtypes, 4 B/elt fp32.
      * gather overhead — the gather datapaths materialize a contiguous
        dequantized fp32 copy of the history (and of the quantized chunk)
        before attending, paying a full write + read of that copy on top
        of the raw read. The contiguous-fp32 gather reads the cache in
        place (masked one-pass softmax, no copy), so its overhead is
        zero — fused vs gather only diverges where a copy exists (every
        paged cell and every quantized cell).
      * the chunk's own fresh KV is read once by both paths; paged adds
        the block-table read.

    Everything is divided by ``chunk``: the steady-state per-prompt-token
    HBM cost of prefilling at this chunk size. q/output traffic is
    identical across paths and excluded.
    """
    elt = kv_code_bytes(kv_dtype)
    row = Hkv * (D + Dv) * elt
    if kv_dtype != "fp32":
        row += Hkv * 2 * SCALE_BYTES
    row_f32 = Hkv * (D + Dv) * F32
    hist = ctx * row
    chunk_bytes = chunk * row
    b = hist + chunk_bytes
    copy = 2 * (ctx + chunk) * row_f32      # write + read of the fp32 copy
    if layout == "paged":
        b += TABLE_BYTES * (-(-ctx // page_size))
        if path == "gather":
            b += copy
    elif path == "gather" and kv_dtype != "fp32":
        b += copy
    return b / chunk
