"""Fused Pallas flash-prefill (DESIGN.md §10): property tests, adversarial
block tables, and the fallback-free engine startup contract.

Parity contract mirrors test_fused_decode: the fused kernels — two-segment
[cache ++ chunk] KV walks, in-kernel positional masking, in-kernel
block-table indexing, in-register dequant — must match the masked-XLA
gather paths to 1e-4 on the exact variant for every random split of
cache_len / chunk_size / page_size, including the degenerate serving
shapes (chunk_size=1 legacy path, cache_len=0 fresh prompt, ragged last
pages, window smaller than one page). The systematic backend matrix lives
in tests/test_conformance.py; this file stress-tests the new kernel's
masking logic and its adversarial-memory behavior.
"""
import logging

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:  # property tests need hypothesis; the rest below do not
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:
    HAVE_HYPOTHESIS = False

import repro.core.attention  # noqa: F401 — registers built-ins
import repro.kernels.kvquant  # noqa: F401 — registers the _q backends
from repro.configs import get_config
from repro.kernels.paged import slot_rows
from repro.kernels.registry import (
    AttentionSpec,
    dispatch_paged_prefill,
    dispatch_prefill,
    resolved_backends,
)
from repro.kernels.flash.ops import fused_paged_prefill_attention_pallas
from repro.models.api import init_model
from repro.numerics.log2exp import apply_pow2_scale, log2exp_lhat, pow2_neg
from repro.numerics.quant import quantize_kv
from repro.serve.engine import ServeEngine

from cells import MODEL_FAMILIES  # noqa: F401 — the shared family table


def _dispatch_pair(q, kc, vc, kn, vn, lens, nv, *, window, rolling,
                   block_q=8, block_k=8):
    """(pallas out, masked_xla out) for one contiguous prefill dispatch."""
    base = AttentionSpec(variant="exact", window=window, block_q=block_q,
                        block_k=block_k)
    out = dispatch_prefill(base.replace(prefill_impl="pallas"), q, kc, vc,
                           kn, vn, lengths=lens, n_valid=nv,
                           rolling=rolling)
    ref = dispatch_prefill(base.replace(prefill_impl="masked_xla"), q, kc,
                           vc, kn, vn, lengths=lens, n_valid=nv,
                           rolling=rolling)
    return out, ref


def _assert_valid_rows_close(out, ref, nv, atol=1e-4):
    for b in range(out.shape[0]):
        n = int(nv[b])
        np.testing.assert_allclose(np.asarray(out)[b, :, :n],
                                   np.asarray(ref)[b, :, :n],
                                   atol=atol, rtol=atol)


# ---------------------------------------------------------------------------
# property checks: random cache_len / chunk / page splits (hypothesis when
# available; a deterministic edge-split sweep always runs)
# ---------------------------------------------------------------------------
def _check_contiguous_split(cache_len, chunk, n_valid, window, seed):
    """The fused kernel's in-kernel masks must agree with the positional
    XLA math on every valid row — rolling buffers included."""
    n_valid = min(n_valid, chunk)
    rng = np.random.default_rng(seed)
    B, H, Hkv, D, Dv = 2, 4, 2, 8, 12
    rolling = window is not None
    span = window if rolling else 20
    q = jnp.asarray(rng.standard_normal((B, H, chunk, D)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, Hkv, span, D)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, Hkv, span, Dv)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, Hkv, chunk, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, Hkv, chunk, Dv)), jnp.float32)
    lens = jnp.asarray([cache_len, max(0, cache_len - 3)], jnp.int32)
    nv = jnp.asarray([n_valid, min(chunk, n_valid + 1)], jnp.int32)
    out, ref = _dispatch_pair(q, kc, vc, kn, vn, lens, nv, window=window,
                              rolling=rolling, block_q=4, block_k=4)
    _assert_valid_rows_close(out, ref, nv)


def _check_paged_split(cache_len, chunk, page_size, window, seed, *,
                       max_blocks=None, lengths=None, n_valid=None,
                       variant="exact", kv_dtype="fp32", block_q=4):
    """Random paged splits — ragged last pages, windows smaller than one
    page, shuffled tables with sentinel tails — pinned against the XLA
    gather path (exact), or against the gather-then-contiguous-kernel
    path on the same page-sized key tiles (expmul, whose blocked result
    depends on the tiling). ``lengths`` / ``n_valid`` give one slot each
    (default: two slots derived from ``cache_len`` and ``chunk``); odd
    slots leave every table entry past their history unallocated."""
    rng = np.random.default_rng(seed)
    H, Hkv, D = 4, 2, 8
    MB = max_blocks or -(-32 // page_size)
    if lengths is None:
        lengths = [min(cache_len, (MB - 1) * page_size),
                   min(max(0, cache_len - 5), (MB - 1) * page_size)]
        n_valid = [chunk, max(1, chunk - 1)]
    B = len(lengths)
    nblk = B * MB + 2
    perm = rng.permutation(nblk)
    bt = np.stack([perm[i * MB:(i + 1) * MB] for i in range(B)])
    for b in range(1, B, 2):  # sentinel tails: short-allocated slots
        bt[b, -(-lengths[b] // page_size):] = nblk
    bt = jnp.asarray(bt.astype(np.int32))
    rows = slot_rows(bt, page_size)
    pool_tokens = nblk * page_size
    q = jnp.asarray(rng.standard_normal((B, H, chunk, D)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((pool_tokens, Hkv, D)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((pool_tokens, Hkv, D)), jnp.float32)
    kn = jnp.asarray(rng.standard_normal((B, Hkv, chunk, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, Hkv, chunk, D)), jnp.float32)
    if variant == "expmul":
        # scores exact in f32 whatever the matmul's width: ExpMul rounds
        # s - m to a power of two, so a last-bit change of one score may
        # flip a weight by 2x, and kernel and oracle tile their matmuls
        # differently (one per 128-key block, one per page)
        q, kp, kn = (jnp.round(x * 8) / 8 for x in (q, kp, kn))
    if kv_dtype != "fp32":
        kp, vp, kn, vn = (quantize_kv(x, kv_dtype) for x in (kp, vp, kn, vn))
    lens = jnp.asarray(lengths, jnp.int32)
    nv = jnp.asarray(n_valid, jnp.int32)
    positions = lens[:, None] + jnp.arange(chunk)[None, :]
    chunk_valid = jnp.arange(chunk)[None, :] < nv[:, None]
    base = AttentionSpec(variant=variant, window=window, block_q=block_q,
                         kv_dtype=kv_dtype)
    oracle = "gather_xla" if variant == "exact" else "gather_pallas"
    out, ref = (dispatch_paged_prefill(
        base.replace(paged_impl=impl), q, kn, vn, kp, vp, rows,
        q_positions=positions, chunk_valid=chunk_valid, lengths=lens,
        block_tables=bt, page_size=page_size)
        for impl in ("pallas", oracle))
    _assert_valid_rows_close(out, ref, nv)


# the serving shapes the issue names explicitly, pinned deterministically
# (these run with or without hypothesis installed)
CONTIGUOUS_EDGE_SPLITS = [
    # (cache_len, chunk, n_valid, window, seed)
    (0, 8, 8, None, 0),     # fresh prompt: empty cache
    (13, 1, 1, None, 1),    # chunk_size=1 legacy tick
    (11, 1, 1, 5, 2),       # legacy tick into a rolling buffer
    (17, 8, 5, 7, 3),       # rolling buffer wrapped, partial chunk
    (3, 8, 8, 7, 4),        # cache shorter than the window span
    (20, 6, 0, None, 5),    # idle slot: n_valid=0
]
PAGED_EDGE_SPLITS = [
    # (cache_len, chunk, page_size, window, seed)
    (0, 8, 4, None, 0),     # fresh prompt through the pool
    (13, 1, 4, None, 1),    # legacy tick, ragged last page
    (26, 5, 8, 3, 2),       # window (3) smaller than one page (8)
    (27, 8, 4, 5, 3),       # ragged last page + window across pages
    (24, 8, 8, None, 4),    # page-aligned history
]


# history walked in 128-token blocks: at page 16 a 20-page table is two
# whole 8-page blocks and one of 4. One slot per length at a block edge;
# ``shift`` deals the chunk's valid counts (0, 1, a whole block) to them,
# so across the cases every length meets every count
BLOCK_LENGTHS = (0, 1, 127, 128, 129, 200, 20 * 16 - 1)
BLOCK_N_VALID = (0, 1, 128)


@pytest.mark.parametrize("variant,window,kv_dtype,shift", [
    ("exact", None, "fp32", 0),
    ("expmul", None, "fp32", 1),
    ("exact", 150, "fp32", 2),     # window floor inside the second block
    ("expmul", 150, "fp32", 0),
    ("exact", None, "int8", 1),
    ("expmul", None, "int8", 2),
])
def test_paged_prefill_block_edges(variant, window, kv_dtype, shift):
    n_valid = [BLOCK_N_VALID[(i + shift) % 3]
               for i in range(len(BLOCK_LENGTHS))]
    _check_paged_split(None, 128, 16, window, 7, max_blocks=20,
                       lengths=list(BLOCK_LENGTHS), n_valid=n_valid,
                       variant=variant, kv_dtype=kv_dtype, block_q=64)


@pytest.mark.parametrize("split", CONTIGUOUS_EDGE_SPLITS,
                         ids=lambda s: f"len{s[0]}-c{s[1]}-w{s[3]}")
def test_contiguous_prefill_edge_splits(split):
    _check_contiguous_split(*split)


@pytest.mark.parametrize("split", PAGED_EDGE_SPLITS,
                         ids=lambda s: f"len{s[0]}-c{s[1]}-p{s[2]}-w{s[3]}")
def test_paged_prefill_edge_splits(split):
    _check_paged_split(*split)


if HAVE_HYPOTHESIS:

    @settings(max_examples=20, deadline=None)
    @given(
        cache_len=st.integers(0, 20),
        chunk=st.integers(1, 9),          # chunk_size=1 is the legacy tick
        n_valid=st.integers(0, 9),
        window=st.sampled_from([None, 3, 7]),
        seed=st.integers(0, 2**16),
    )
    def test_contiguous_prefill_matches_xla(cache_len, chunk, n_valid,
                                            window, seed):
        _check_contiguous_split(cache_len, chunk, n_valid, window, seed)

    @settings(max_examples=20, deadline=None)
    @given(
        cache_len=st.integers(0, 30),
        chunk=st.integers(1, 8),
        page_size=st.sampled_from([4, 8]),   # ragged last pages
        window=st.sampled_from([None, 3, 5, 11]),  # 3 < page: in-page floor
        seed=st.integers(0, 2**16),
    )
    def test_paged_prefill_matches_xla(cache_len, chunk, page_size, window,
                                       seed):
        _check_paged_split(cache_len, chunk, page_size, window, seed)


# ---------------------------------------------------------------------------
# the key tiles ExpMul sees: page-sized inside the kernel's 128-key blocks
# ---------------------------------------------------------------------------
def _expmul_walk(q, segments, tile, scale):
    """Plain-jnp ExpMul FlashAttention over ``segments`` — (keys, values,
    valid mask) triples walked in order — in sequential ``tile``-key steps,
    the online-softmax recurrence of ``online_softmax_tile``."""
    m = jnp.full((q.shape[0], 1), -1e30, jnp.float32)
    l = jnp.zeros_like(m)
    acc = jnp.zeros((q.shape[0], segments[0][1].shape[1]), jnp.float32)
    for k, v, valid in segments:
        pad = -k.shape[0] % tile
        k, v = (jnp.pad(x, ((0, pad), (0, 0))) for x in (k, v))
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
        for t in range(0, k.shape[0], tile):
            mask = valid[:, t:t + tile]
            s = jnp.where(mask, q @ k[t:t + tile].T * scale, -1e30)
            m_new = jnp.maximum(m, s.max(axis=1, keepdims=True))
            r = log2exp_lhat(m - m_new)
            p = jnp.where(mask, pow2_neg(log2exp_lhat(s - m_new)), 0.0)
            l = apply_pow2_scale(l, r) + p.sum(axis=1, keepdims=True)
            acc = apply_pow2_scale(acc, jnp.broadcast_to(r, acc.shape)) \
                + p @ v[t:t + tile]
            m = m_new
    return acc / jnp.where(l == 0, 1.0, l)


def test_expmul_block_keeps_page_tiles():
    """The 128-key block step must compute the page-tile walk: ExpMul's
    result depends on how keys are tiled (each tile's weights are rounded
    against the running max after it), and the benchmark's reference
    walks 16-key tiles. A 128-key-tile walk of the same keys must differ,
    or the check has no teeth."""
    rng = np.random.default_rng(11)
    ps, MB, length, chunk, n_valid, D = 16, 20, 200, 128, 100, 8
    # grid-valued q and k: every score is exact in f32 whatever the width
    # of the matmul that forms it, so the comparison sees tiling alone
    grid = lambda *shape: jnp.asarray(  # noqa: E731
        np.round(rng.standard_normal(shape) * 16) / 8, jnp.float32)
    q = grid(1, 1, chunk, D)
    bt = jnp.asarray(rng.permutation(MB)[None].astype(np.int32))
    kp, kn = grid(MB * ps, 1, D), grid(1, 1, chunk, D)
    vp = jnp.asarray(rng.standard_normal((MB * ps, 1, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((1, 1, chunk, D)), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    out = fused_paged_prefill_attention_pallas(
        q, kn, vn, kp, vp, bt, jnp.asarray([length], jnp.int32),
        jnp.asarray([n_valid], jnp.int32), page_size=ps, scale=scale,
        variant="expmul")[0, 0, :n_valid]
    hist = slot_rows(bt, ps)[0, :length]
    rows = np.arange(n_valid)[:, None]
    segments = [
        (kp[hist, 0], vp[hist, 0], jnp.ones((n_valid, length), bool)),
        (kn[0, 0, :n_valid], vn[0, 0, :n_valid],
         jnp.asarray(rows >= np.arange(n_valid)[None, :])),
    ]
    q2 = q[0, 0, :n_valid]
    pages = _expmul_walk(q2, segments, ps, scale)
    blocks = _expmul_walk(q2, segments, 128, scale)
    np.testing.assert_allclose(np.asarray(out), np.asarray(pages),
                               atol=1e-5, rtol=1e-5)
    assert float(jnp.max(jnp.abs(blocks - pages))) > 1e-5


# ---------------------------------------------------------------------------
# adversarial block tables: unowned-pool poisoning (mirrors PR-4's decode)
# ---------------------------------------------------------------------------
def test_fused_paged_prefill_ignores_unallocated_pool_rows():
    """Sentinel table entries are clamped to a real block by the kernel's
    index map; corrupting every row the tables do *not* own (including the
    clamp target) must not leak into any chunk position of any slot."""
    rng = np.random.default_rng(3)
    B, H, Hkv, D, ps, nblk, MB, chunk = 2, 4, 2, 8, 4, 13, 5, 6
    perm = rng.permutation(nblk)
    bt = np.stack([perm[:MB], perm[MB:2 * MB]]).astype(np.int32)
    bt[1, -2:] = nblk  # slot 1 short-allocated: sentinel tail
    bt = jnp.asarray(bt)
    lens = jnp.asarray([17, 9], jnp.int32)
    nv = jnp.asarray([6, 4], jnp.int32)
    rows = slot_rows(bt, ps)
    pool_tokens = nblk * ps
    q = jnp.asarray(rng.standard_normal((B, H, chunk, D)), jnp.float32)
    kp = np.asarray(rng.standard_normal((pool_tokens, Hkv, D)), np.float32)
    vp = np.asarray(rng.standard_normal((pool_tokens, Hkv, D)), np.float32)
    kn = jnp.asarray(rng.standard_normal((B, Hkv, chunk, D)), jnp.float32)
    vn = jnp.asarray(rng.standard_normal((B, Hkv, chunk, D)), jnp.float32)
    positions = lens[:, None] + jnp.arange(chunk)[None, :]
    chunk_valid = jnp.arange(chunk)[None, :] < nv[:, None]
    spec = AttentionSpec(variant="exact", paged_impl="pallas", block_q=4)

    def run(kpool, vpool):
        return dispatch_paged_prefill(
            spec, q, kn, vn, jnp.asarray(kpool), jnp.asarray(vpool), rows,
            q_positions=positions, chunk_valid=chunk_valid, lengths=lens,
            block_tables=bt, page_size=ps)

    out1 = run(kp, vp)
    owned = set()
    for b in range(B):
        n_pages = -(-int(lens[b]) // ps)
        owned |= {int(x) for x in np.asarray(bt)[b, :n_pages]}
    poison_k, poison_v = kp.copy(), vp.copy()
    for blk in set(range(nblk)) - owned:
        poison_k[blk * ps:(blk + 1) * ps] = 1e9
        poison_v[blk * ps:(blk + 1) * ps] = -1e9
    out2 = run(poison_k, poison_v)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# engine level: fallback-free startup + fused-prefill stream equality
# ---------------------------------------------------------------------------
def test_engine_startup_log_is_fallback_free_for_pallas(caplog):
    """ISSUE-5 satellite: the ServeEngine startup backend-resolution log
    must contain no fallback lines ('-> runs') for attention_impl=pallas —
    a silently re-introduced alias registration fails here."""
    import repro.serve.engine as engine_mod

    cfg = get_config("qwen2-0.5b", smoke=True, dtype="float32",
                     param_dtype="float32", attention_variant="exact")
    params = init_model(jax.random.PRNGKey(0), cfg)
    engine_mod._LOGGED_BACKENDS.clear()
    with caplog.at_level(logging.INFO, logger="repro.serve"):
        ServeEngine(params, cfg, slots=2, max_len=64, chunk_size=8,
                    kv_layout="paged", page_size=8, kv_dtype="int8",
                    attention_impl="pallas")
    assert not any("-> runs" in r.message for r in caplog.records), [
        r.message for r in caplog.records]
    # and the registry agrees: zero declared fallbacks across the family
    for row in resolved_backends(
            AttentionSpec(impl="pallas", kv_dtype="int8"), paged=True):
        assert not row["fallback"], row
    # the start-up log names the history block the fused prefill walks
    assert any(r.message.startswith("attention paged prefill:")
               and "16 pages of 8 per block (128 tokens)" in r.message
               for r in caplog.records), [r.message for r in caplog.records]


@pytest.mark.parametrize("impl,kv_dtype,page_size,block", [
    ("pallas", "fp32", 16, "8 pages of 16 per block (128 tokens)"),
    ("pallas", "int8", 16, "8 pages of 16 per block (128 tokens)"),
    ("pallas", "fp32", 48, "1 pages of 48 per block (48 tokens)"),
    ("gather_xla", "fp32", 16, None),
])
def test_resolved_backends_note_the_prefill_block(impl, kv_dtype, page_size,
                                                  block):
    """The fused paged prefill's row notes the 128-token block it walks
    (one page where the page does not divide 128); other rows and other
    impls carry no block note."""
    spec = AttentionSpec(impl=impl, kv_dtype=kv_dtype)
    rows = {r["kind"]: r for r in resolved_backends(spec, paged=True,
                                                    page_size=page_size)}
    for kind, row in rows.items():
        if kind == "paged prefill" and block:
            assert block in row["note"], row
        else:
            assert "per block" not in row["note"], row


@pytest.mark.parametrize("kv_layout,kv_dtype", [
    ("paged", "int8"),        # the fully fused serving pair
    ("contiguous", "fp32"),   # contiguous prefill kernel in the engine
])
def test_engine_fused_prefill_matches_gather_streams(kv_layout, kv_dtype):
    """Temp-0 token streams must be identical when the prefill tick runs
    the fused kernels instead of the XLA gather math."""
    cfg = get_config("qwen2-0.5b", smoke=True, dtype="float32",
                     param_dtype="float32", attention_variant="exact")
    params = init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    prompts = [list(rng.integers(1, 200, size=n)) for n in (5, 19, 3)]
    kw = dict(slots=2, max_len=64, chunk_size=8, kv_layout=kv_layout,
              kv_dtype=kv_dtype)
    if kv_layout == "paged":
        kw["page_size"] = 8

    def streams(**extra):
        eng = ServeEngine(params, cfg, **kw, **extra)
        reqs = [eng.submit(p, 6, rid=i) for i, p in enumerate(prompts)]
        eng.run()
        assert all(r.done for r in reqs)
        return [r.out for r in reqs]

    assert streams() == streams(attention_impl="pallas")
