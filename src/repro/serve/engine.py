"""Slot-based serving engine: chunked prefill + continuous decode batching
over a contiguous or paged KV cache.

A fixed pool of ``slots`` shares two compiled graphs (DESIGN.md §6):

  prefill step   every slot contributes up to ``chunk_size`` tokens — the
                 remaining prompt for prefilling slots, the single current
                 token for decode-ready slots (a decode is just a 1-valid
                 chunk), zero for idle slots. All valid positions of every
                 layer's KV cache are written in one pass, so a prompt of
                 length L is absorbed in ceil(L / chunk_size) engine steps
                 instead of L teacher-forced ticks, and the step that
                 consumes the last prompt token also emits the first
                 sampled token.
  decode tick    when no slot is prefilling, the cheap single-token graph
                 advances all active slots by one sampled token.

Finished slots free immediately and queued requests join on the next step —
vLLM-style continuous batching in its TPU-friendly fixed-shape form (the
chunk size is static, so each graph compiles once). The attention variant
(exact vs the paper's ExpMul) comes from the model config via the backend
registry.

``kv_layout`` selects the KV memory model (DESIGN.md §7):

  "contiguous"   one max_len-sized cache region per slot — memory scales
                 with slots x max_len regardless of actual lengths.
  "paged"        attention caches are flat physical block pools shared by
                 all slots; a host-side ``BlockPool`` grows each sequence's
                 block table on demand. When a reservation cannot fit, the
                 youngest active request is preempted: its blocks are
                 evicted and it is requeued with prompt + generated tokens
                 as the new teacher-forced prefix (recompute-style
                 resumption — deterministic at temperature 0, so token
                 streams are unchanged). Recurrent block kinds keep per-slot
                 O(1) state and bypass paging.

``prefix_cache`` (paged only) turns on automatic shared-prefix KV caching
(DESIGN.md §11): full pages are indexed by their whole token prefix as they
are written, admission splices the longest indexed prefix of a new prompt
into the slot's block table and advances the prefill cursor past it — the
paged backends take arbitrary block tables, so the hit skips the prefix's
prefill FLOPs and KV HBM writes outright, quantized layouts included. The
resume cursor is trimmed down to the chunk grid so the remaining prefill
chunks tile exactly as a cold run's would, keeping warm temp-0 streams
bit-identical to cold (the ExpMul blocked softmax is tile-dependent by
construction). Divergent writes into a shared partial tail block trigger
copy-on-write. Default (None) = auto: on for paged attention-only configs,
off otherwise; ``prefix_cache=True`` on an unsupported config raises.

Both layouts run the same scheduler and sampling sequence, so with an
adequately sized pool the paged engine emits bit-identical token streams to
the contiguous one. ``chunk_size=1`` falls back to the legacy behavior:
prompts are teacher-forced one token per tick through the decode graph.

``kv_dtype`` selects the KV-cache storage precision (DESIGN.md §8):
"fp32" (unquantized, the default — streams bit-identical to earlier PRs),
or "int8"/"fp8" which store codes + per-row float32 scales and attend
through the registry's fused-dequant ``*_q`` backends. For a paged engine
an explicit ``pool_blocks`` is an **unquantized-equivalent byte budget**
(what that many blocks cost at ``kv_dtype="fp32"``, i.e. stored in
``cfg.dtype``): the same bytes hold more blocks quantized — ~3.2x for
float32-served models (codes are 1 byte; the f32 scale rows take the
rest), ~1.9x when the unquantized cache would be bfloat16 — so
quantization multiplies co-resident tokens (and cuts preemptions)
instead of shrinking the footprint silently. ``memory_stats()`` reports
both token and real-byte accounting (codes + scale pools). Quantized
dtypes are valid only for attention-only decoder configs — see
``validate_kv_dtype``.

``attention_impl`` overrides the config's backend family for the whole
engine; ``"pallas"`` serves *both* ticks fused — decode on the paged/
quantized flash-decode kernels (DESIGN.md §9) and chunked prefill on the
flash-prefill kernels (DESIGN.md §10: two-segment [cache ++ chunk] walks,
in-kernel block tables, in-register dequant) — with zero registry
fallbacks behind the knob. Non-obvious backend resolutions — declared
fallbacks (none registered today) and the CPU interpret-mode caveat — are
logged once at startup via ``registry.resolved_backends``.

Fault tolerance (DESIGN.md §13): every request ends with a
``finish_reason`` in {"length", "deadline", "cancelled", "failed",
"preempt_limit"}, surfaced per-reason through ``metrics_snapshot()`` /
``prometheus_text()``. Requests carry optional budgets — ``deadline_steps``
(engine steps from first admission) and ``deadline_s`` (wall clock from
submit) — enforced by a shared ``reliability.DeadlineWatchdog`` at the top
of every tick; ``cancel(rid)`` unwinds a request at any lifecycle stage
(queued, mid-prefill, mid-decode, or preempted) through the refcounted
pool. A host-side sentinel checks each tick's logits (and sampled tokens)
for non-finite values: ``nan_guard="quarantine"`` (default) fails only the
offending request — its blocks are freed *and de-indexed from the radix
cache* so a corrupted page can never be splice-reused — while co-resident
temp-0 streams stay bit-identical to a fault-free run; ``"strict"`` raises
``NonFiniteLogitsError`` instead. The deterministic chaos harness
(``serve/faults.py``) drives these paths via seedable injection points;
``serve/snapshot.py`` adds crash-consistent engine snapshot/restore
(mid-flight streams continue bit-identically and the cached prefix tier
survives restarts).
"""
from __future__ import annotations

import dataclasses
import logging
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.registry import AttentionSpec, resolved_backends

from repro.models.api import (
    copy_paged_block,
    decode_step,
    decode_step_paged,
    init_decode_state,
    init_paged_state,
    poison_paged_block,
    prefill,
    prefill_paged,
)
from repro.numerics.quant import KV_DTYPES
from repro.reliability import DeadlineWatchdog
from repro.serve.faults import fault_point
from repro.serve.metrics import (
    MS_BUCKETS,
    PID_ENGINE,
    PID_REQUESTS,
    MetricsRegistry,
    install_dispatch_counters,
)
from repro.serve.paged import BlockPool, blocks_for, kv_token_bytes
from repro.serve.sampling import sample_tokens

logger = logging.getLogger("repro.serve")

# backend-resolution lines already reported this process (log once per
# distinct message, not once per engine — benches build many engines)
_LOGGED_BACKENDS: set[str] = set()


def _log_resolved_backends(cfg, paged: bool, page_size: int):
    """One startup line per non-obvious backend resolution (DESIGN.md §9):
    declared fallbacks (a requested impl routing to another impl's math)
    and the registry's notes (the CPU interpret-mode caveat for Pallas
    kernels, the history block the fused paged prefill walks) — so a
    config knob can never silently mean something else."""
    for row in resolved_backends(AttentionSpec.from_config(cfg), paged=paged,
                                 page_size=page_size):
        if not (row["fallback"] or row["note"]):
            continue
        msg = f"attention {row['kind']}: requested {row['requested']!r}"
        if row["fallback"]:
            msg += f" -> runs {row['resolved']!r}"
        if row["note"]:
            msg += f" [{row['note']}]"
        if msg not in _LOGGED_BACKENDS:
            _LOGGED_BACKENDS.add(msg)
            logger.info(msg)


def stream_match_rate(ref_streams, streams) -> float:
    """Token-level exact-match rate across paired temp-0 streams (the
    quantized-KV fidelity metric — DESIGN.md §8)."""
    return float(np.mean([
        np.mean([a == b for a, b in zip(x, y)]) if len(x) else 1.0
        for x, y in zip(ref_streams, streams)
    ]))


def validate_kv_dtype(cfg, kv_dtype: str | None = None) -> str:
    """Resolve and validate a KV-cache storage dtype for serving ``cfg``.

    Quantized dtypes require an attention-only decoder: recurrent block
    kinds (rglru/mlstm/slstm) carry O(1) state that is not a KV cache, and
    encoder-decoder cross K/V are recomputed activations — both would
    silently bypass quantization, so they are rejected loudly instead
    (DESIGN.md §8). Returns the resolved dtype string.
    """
    kv_dtype = kv_dtype or cfg.kv_dtype
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"unknown kv_dtype {kv_dtype!r}; "
                         f"choose one of {KV_DTYPES}")
    if kv_dtype != "fp32":
        rec = sorted(set(cfg.block_pattern) - {"attn"})
        if rec:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} requires an attention-only block "
                f"pattern, but {cfg.name!r} mixes in {rec} blocks whose "
                f"recurrent state is not a KV cache and would silently "
                f"bypass quantization; serve this arch with kv_dtype='fp32'")
        if cfg.encoder_layers:
            raise ValueError(
                f"kv_dtype={kv_dtype!r} targets decoder-only configs; "
                f"{cfg.name!r} is encoder-decoder and its cross-attention "
                f"K/V are recomputed activations, not a cache — serve it "
                f"with kv_dtype='fp32'")
    return kv_dtype


def analytic_prefill_flops(cfg, start: int, end: int) -> int:
    """Analytic decoder FLOPs to prefill positions [start, end) on top of a
    resident ``start``-token prefix: 2·params per token for the linear path
    plus 4·H·hd per (query, key) causal pair for scores + weighted sum —
    the standard 6ND-style estimate restricted to a position range, used to
    price what a prefix-cache hit skips (BENCH_serve.json
    ``prefill_flops_skipped``)."""
    n = max(0, end - start)
    if n == 0:
        return 0
    flops = 2 * cfg.active_param_count() * n
    attn_layers = sum(1 for k in cfg.pattern_for() if k == "attn")
    span = (end * (end + 1) - start * (start + 1)) // 2
    flops += 4 * cfg.num_heads * cfg.resolved_head_dim() * attn_layers * span
    return int(flops)


# terminal request states (DESIGN.md §13): every finished request carries
# exactly one, and metrics_snapshot()["finish_reasons"] counts each
FINISH_REASONS = ("length", "deadline", "cancelled", "failed",
                  "preempt_limit")

# nan_guard modes: quarantine the offending request (default), raise on
# first fault, or skip the sentinel entirely
NAN_GUARDS = ("quarantine", "strict", "off")


class NonFiniteLogitsError(RuntimeError):
    """Raised by ``nan_guard="strict"`` when a tick produces non-finite
    logits (or an out-of-range sampled token) for an active request."""


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list
    max_new: int
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    finish_reason: str | None = None  # one of FINISH_REASONS once done
    deadline_steps: int | None = None  # engine-step budget from admission
    deadline_s: float | None = None    # wall-clock budget from submit
    submit_time: float | None = None   # host wall clock of submit()
    pos: int = 0            # prefill cursor into ``prefill_toks``
    first_token_step: int | None = None  # engine step that produced out[0]
    preemptions: int = 0    # times this request was evicted and requeued
    admit_order: int = -1   # admission sequence number (victim selection)
    # teacher-forced prefix: the prompt, extended with already-generated
    # tokens after a preemption (recompute-style resumption)
    prefill_toks: list = dataclasses.field(default_factory=list)
    admit_step: int | None = None  # engine step of first admission (TTFT base)
    admit_time: float | None = None   # host wall clock of first admission
    last_token_step: int | None = None  # engine step of latest sample (TPOT)
    last_token_time: float | None = None
    prefix_hit: int = 0     # tokens skipped via prefix-cache hits (cumulative)
    prefill_kv_bytes: int = 0  # KV bytes this request actually wrote in prefill
    registered_blocks: int = 0  # full pages of this slot already indexed


class ServeEngine:
    def __init__(self, params, cfg, *, slots: int = 8, max_len: int = 512,
                 chunk_size: int = 64, temperature: float = 0.0,
                 seed: int = 0, kv_layout: str = "contiguous",
                 page_size: int | None = None,
                 pool_blocks: int | None = None,
                 kv_dtype: str | None = None,
                 attention_impl: str | None = None,
                 prefix_cache: bool | None = None,
                 metrics: MetricsRegistry | None = None,
                 trace: bool = False,
                 nan_guard: str = "quarantine",
                 deadline_steps: int | None = None,
                 deadline_s: float | None = None,
                 max_preemptions: int | None = None):
        # loud argument validation (ISSUE-9 satellite): these used to be
        # bare asserts, which vanish under ``python -O``
        if kv_layout not in ("contiguous", "paged"):
            raise ValueError(f"kv_layout must be 'contiguous' or 'paged', "
                             f"got {kv_layout!r}")
        if nan_guard not in NAN_GUARDS:
            raise ValueError(f"nan_guard must be one of {NAN_GUARDS}, "
                             f"got {nan_guard!r}")
        if slots < 1:
            raise ValueError(f"slots must be >= 1, got {slots}")
        if max_len < 2:
            raise ValueError(f"max_len must be >= 2 (one prompt token plus "
                             f"one generated), got {max_len}")
        if int(chunk_size) < 1:
            raise ValueError(f"chunk_size must be >= 1, got {chunk_size}")
        if max_preemptions is not None and max_preemptions < 0:
            raise ValueError(
                f"max_preemptions must be >= 0, got {max_preemptions}")
        # observability (DESIGN.md §12): the registry is the single owner
        # of every serving counter — memory_stats()/PoolStats are views.
        # ``trace`` gates span/event recording only; counters, histograms
        # and metrics_snapshot() are always live, and every span is a
        # profiler annotation, so the phases of a tick lie on the device
        # trace's clock
        self.metrics = metrics if metrics is not None else MetricsRegistry(
            trace=trace)
        if self.metrics.annotate is None:
            self.metrics.annotate = jax.profiler.TraceAnnotation
        self.kv_dtype = validate_kv_dtype(cfg, kv_dtype)
        cfg = cfg.replace(kv_dtype=self.kv_dtype)
        if attention_impl is not None:
            # one knob selects the whole backend family (full/prefill/
            # decode/paged follow ``impl`` through AttentionSpec resolution;
            # "pallas" turns on the fused paged decode of DESIGN.md §9)
            cfg = cfg.replace(attention_impl=attention_impl)
        self.attention_impl = cfg.attention_impl
        ps = int(page_size or cfg.page_size)
        _log_resolved_backends(cfg, kv_layout == "paged", ps)
        self.params = params
        self.cfg = cfg
        self.slots = slots
        self.max_len = max_len
        self.chunk_size = int(chunk_size)
        self.temperature = temperature
        # base sampling key: per-request keys are folded from it each tick
        # (see _sample_keys) so temp>0 streams are scheduling-invariant
        self.key = jax.random.PRNGKey(seed)
        self.kv_layout = kv_layout
        self.paged = kv_layout == "paged"
        # shared-prefix caching (DESIGN.md §11): needs paged physical blocks
        # to splice, and an attention-only pattern — recurrent per-slot state
        # is not reconstructible from spliced KV pages, so a hit would skip
        # prefill the recurrent layers still need
        attn_only = set(cfg.block_pattern) == {"attn"} and not cfg.encoder_layers
        if prefix_cache:
            if not self.paged:
                raise ValueError(
                    "prefix_cache=True requires kv_layout='paged': the "
                    "contiguous layout has no shared physical blocks to "
                    "dedupe — serve with kv_layout='paged' or drop the flag")
            if not attn_only:
                rec = sorted(set(cfg.block_pattern) - {"attn"})
                raise ValueError(
                    f"prefix_cache=True requires an attention-only block "
                    f"pattern, but {cfg.name!r} mixes in {rec} blocks whose "
                    f"recurrent state cannot be reconstructed from spliced "
                    f"KV pages; serve this arch with prefix_cache=False")
        self.prefix_cache = (bool(prefix_cache) if prefix_cache is not None
                             else self.paged and attn_only)
        # bytes per resident token across all attention layers (codes +
        # scale pools for quantized dtypes) — the unit of every *_bytes stat
        self.token_bytes = kv_token_bytes(cfg, self.kv_dtype)
        if self.paged:
            max_blocks = blocks_for(max_len, ps)
            requested = int(pool_blocks or cfg.pool_blocks or 0)
            if requested:
                # ``pool_blocks`` is an unquantized-equivalent byte
                # budget (what that many blocks cost at kv_dtype="fp32",
                # i.e. stored in cfg.dtype): a quantized pool spends the
                # same bytes on proportionally more physical blocks — the
                # KV-quantization capacity win (DESIGN.md §8; ~3.2x for
                # float32-served models, ~1.9x for bfloat16 caches)
                n_pool = max(1, requested * kv_token_bytes(cfg, "fp32")
                             // self.token_bytes)
            else:
                n_pool = slots * max_blocks  # fully provisioned
            self.page_size = ps
            self.pool = BlockPool(n_pool, ps, slots, max_blocks,
                                  token_bytes=self.token_bytes,
                                  prefix_cache=self.prefix_cache,
                                  metrics=self.metrics)
            self.state = init_paged_state(cfg, slots, n_pool, ps)

            # named, so the device trace's modules read jit_serve_*
            def serve_cow_copy(state, src, dst):
                return copy_paged_block(state, self.cfg, src, dst,
                                        page_size=ps)

            def serve_decode_step(params, state, toks, lens, bt):
                return decode_step_paged(params, state, toks, lens, bt,
                                         self.cfg, page_size=ps)

            def serve_prefill_step(params, state, toks, lens, nv, bt):
                return prefill_paged(params, state, toks, lens, nv, bt,
                                     self.cfg, page_size=ps)

            self._cow_copy = jax.jit(serve_cow_copy)
        else:
            self.page_size = 0
            self.pool = None
            self.state = init_decode_state(cfg, slots, max_len)

            def serve_decode_step(params, state, toks, lens):
                return decode_step(params, state, toks, lens, self.cfg)

            def serve_prefill_step(params, state, toks, lens, nv):
                return prefill(params, state, toks, lens, nv, self.cfg)

        self._decode = jax.jit(serve_decode_step)
        self._prefill = jax.jit(serve_prefill_step)
        self.lengths = np.zeros((slots,), np.int32)
        self.cur_tok = np.zeros((slots,), np.int32)
        self.requests: list[Request | None] = [None] * slots
        self.queue: list[Request] = []
        self._admit_seq = 0
        # fault tolerance (DESIGN.md §13): per-request deadline budgets are
        # enforced by the shared reliability watchdog at the top of every
        # tick; the defaults below apply to submits that don't override
        self.nan_guard = nan_guard
        self.default_deadline_steps = deadline_steps
        self.default_deadline_s = deadline_s
        self.max_preemptions = max_preemptions
        self.deadlines = DeadlineWatchdog()
        self._rids: set = set()     # every rid ever submitted (dup check)
        self._next_rid = 0          # auto-assigned rids are monotonic
        self._poison = None         # lazily jitted kv_corrupt injector half
        self._scrub = None          # lazily jitted quarantine page scrubber
        # lifecycle counters live in the metrics registry (single-owner
        # contract, §12); the legacy attribute names are properties below
        m = self.metrics
        self._c_ticks = m.counter("serve_steps_total")
        self._c_prefill_steps = m.counter("serve_prefill_steps_total")
        self._c_decode_steps = m.counter("serve_decode_steps_total")
        self._c_prompt_tokens = m.counter("serve_prompt_tokens_total")
        self._c_recompute = m.counter("serve_recompute_tokens_total")
        self._c_generated = m.counter("serve_tokens_generated_total")
        self._c_preemptions = m.counter("serve_preemptions_total")
        self._c_hit_tokens = m.counter("serve_prefix_hit_tokens_total")
        self._c_flops_skipped = m.counter("serve_prefill_flops_skipped_total")
        self._c_submitted = m.counter("serve_requests_submitted_total")
        self._c_finished = m.counter("serve_requests_finished_total")
        # per-terminal-state counters (§13): pre-created so snapshots and
        # the Prometheus exposition always carry every reason, zeros
        # included
        self._c_reason = {
            reason: m.counter("serve_finish_reasons_total", reason=reason)
            for reason in FINISH_REASONS
        }
        self._c_quarantined = m.counter("serve_requests_quarantined_total")
        self._g_peak_active = m.gauge("serve_peak_active_tokens")
        self._g_peak_kv = m.gauge("serve_peak_kv_used_tokens")
        self._g_queue = m.gauge("serve_queue_depth")
        # TTFT/TPOT: engine steps are the exact scheduling-level signal on
        # the CPU proxy; the ms twins are host wall clock (no device syncs
        # beyond the per-tick host transfer the engine already performs),
        # TTFT from submit so that queue wait counts
        self._h_ttft_steps = m.histogram("serve_ttft_steps")
        self._h_tpot_steps = m.histogram("serve_tpot_steps")
        self._h_ttft_ms = m.histogram("serve_ttft_ms", buckets=MS_BUCKETS)
        self._h_tpot_ms = m.histogram("serve_tpot_ms", buckets=MS_BUCKETS)
        self._now = 0.0  # host timestamp taken once per tick
        m.name_track(PID_ENGINE, 0, "engine steps")
        install_dispatch_counters(self.metrics)
        # constructor record (DESIGN.md §13): serve/snapshot.py rebuilds an
        # identically shaped engine from exactly these kwargs, so the
        # restored pool geometry and compiled graphs match the snapshot
        self._ctor = {
            "slots": slots, "max_len": max_len,
            "chunk_size": self.chunk_size, "temperature": temperature,
            "seed": seed, "kv_layout": kv_layout,
            "page_size": page_size, "pool_blocks": pool_blocks,
            "kv_dtype": self.kv_dtype, "attention_impl": attention_impl,
            "prefix_cache": self.prefix_cache, "nan_guard": nan_guard,
            "deadline_steps": deadline_steps, "deadline_s": deadline_s,
            "max_preemptions": max_preemptions,
        }

    # -- request lifecycle --------------------------------------------------
    def submit(self, prompt, max_new: int, rid: int | None = None, *,
               deadline_steps: int | None = None,
               deadline_s: float | None = None) -> Request:
        """Queue a request. Raises ``ValueError`` (never a stripped-out
        assert) on an empty/oversized prompt, ``max_new < 1``, or a
        duplicate ``rid``. ``deadline_steps`` bounds engine steps from
        first admission, ``deadline_s`` wall-clock seconds from this call
        (None falls back to the engine defaults); an expired request
        finishes with ``finish_reason="deadline"`` and whatever tokens it
        produced."""
        with self.metrics.span("serve.submit"):
            prompt = list(prompt)
            if not prompt:
                raise ValueError("empty prompt: a request needs at least one "
                                 "token to produce logits")
            if len(prompt) > self.max_len - 1:
                raise ValueError(
                    f"prompt length {len(prompt)} exceeds max_len - 1 = "
                    f"{self.max_len - 1} (one position must remain for the "
                    f"first sampled token)")
            if max_new < 1:
                raise ValueError(f"max_new must be >= 1, got {max_new}")
            deadline_steps = (deadline_steps if deadline_steps is not None
                              else self.default_deadline_steps)
            deadline_s = (deadline_s if deadline_s is not None
                          else self.default_deadline_s)
            if deadline_steps is not None and deadline_steps < 1:
                raise ValueError(
                    f"deadline_steps must be >= 1, got {deadline_steps}")
            if deadline_s is not None and deadline_s < 0:
                raise ValueError(f"deadline_s must be >= 0, got {deadline_s}")
            if rid is None:
                rid = self._next_rid
            elif rid in self._rids:
                raise ValueError(
                    f"duplicate rid {rid}: request ids must be unique per "
                    f"engine (auto-assignment never collides; explicit rids "
                    f"are the caller's responsibility)")
            self._rids.add(rid)
            self._next_rid = max(self._next_rid, rid + 1)
            req = Request(rid, prompt, max_new, prefill_toks=list(prompt),
                          deadline_steps=deadline_steps, deadline_s=deadline_s,
                          submit_time=time.perf_counter())
            if deadline_s is not None:
                self.deadlines.arm(rid, wall_budget=deadline_s,
                                   wall_base=req.submit_time)
            self.queue.append(req)
            self._c_submitted.inc()
            self._g_queue.set(len(self.queue))
            return req

    def cancel(self, rid: int) -> bool:
        """Cancel a request wherever it is in its lifecycle: still queued,
        mid-prefill, mid-decode, or sitting requeued after a preemption.
        An active slot is unwound through the refcounted pool (completed
        full pages are still indexed first — their content is valid, so
        the prefix tier keeps the work). Returns False when ``rid`` is not
        live (unknown, or already finished)."""
        return self._terminate(rid, "cancelled")

    def _terminate(self, rid: int, reason: str) -> bool:
        """Move a live request to a terminal state (cancel / deadline)."""
        for i, req in enumerate(self.queue):
            if req.rid == rid:
                self.queue.pop(i)
                self._g_queue.set(len(self.queue))
                self._finalize_request(req, reason)
                if self.metrics.trace and req.admit_step is not None:
                    self.metrics.end(f"req {req.rid}", pid=PID_REQUESTS,
                                     tid=req.rid, step=self.ticks,
                                     tokens=len(req.out), reason=reason)
                return True
        for s in range(self.slots):
            req = self.requests[s]
            if req is not None and req.rid == rid:
                self._release_slot(s, reason)
                return True
        return False

    def _finalize_request(self, req: Request, reason: str):
        """Terminal-state bookkeeping shared by every finish path."""
        req.done = True
        req.finish_reason = reason
        self.deadlines.disarm(req.rid)
        self._c_finished.inc()
        self._c_reason[reason].inc()

    def _release_slot(self, s: int, reason: str):
        """Finish the request in slot ``s`` with ``reason`` and free the
        slot. Valid terminal states register completed pages into the
        prefix tier (their KV is correct — a cancelled or expired request
        still did real work); a quarantine (``"failed"``) instead
        de-indexes and frees every block so suspect content can never be
        splice-reused (DESIGN.md §13)."""
        req = self.requests[s]
        self._finalize_request(req, reason)
        self.requests[s] = None
        if self.metrics.trace:
            self.metrics.end(f"req {req.rid}", pid=PID_REQUESTS,
                             tid=req.rid, step=self.ticks,
                             tokens=len(req.out),
                             preemptions=req.preemptions, reason=reason)
        if self.paged:
            if reason == "failed":
                self._c_quarantined.inc()
                self._scrub_slot(s)
                self.pool.quarantine_slot(s)
            else:
                if self.prefix_cache:
                    self._register_full_pages(s, req)
                self.pool.free_slot(s)

    def _expire_deadlines(self):
        """Sweep the deadline watchdog (top of every tick): expired
        requests finish with ``finish_reason="deadline"`` and their slots
        free immediately, so a stuck or over-budget request can never pin
        pool blocks or a batch slot indefinitely."""
        for rid in self.deadlines.expired(self.ticks, self._now):
            self._terminate(rid, "deadline")

    # -- legacy counter attributes: read-through registry views (§12) -------
    @property
    def ticks(self) -> int:
        """Total engine steps (prefill + decode)."""
        return self._c_ticks.value

    @property
    def prefill_steps(self) -> int:
        return self._c_prefill_steps.value

    @property
    def decode_steps(self) -> int:
        return self._c_decode_steps.value

    @property
    def prompt_tokens(self) -> int:
        """Prompt tokens absorbed via chunked prefill."""
        return self._c_prompt_tokens.value

    @property
    def recompute_tokens(self) -> int:
        """Generated tokens re-prefilled after a preemption."""
        return self._c_recompute.value

    @property
    def tokens_generated(self) -> int:
        return self._c_generated.value

    @property
    def preemptions(self) -> int:
        return self._c_preemptions.value

    @property
    def prefix_hit_tokens(self) -> int:
        """Prompt tokens skipped via prefix-cache hits."""
        return self._c_hit_tokens.value

    @property
    def prefill_flops_skipped(self) -> int:
        """Analytic FLOPs those hit tokens would have cost."""
        return self._c_flops_skipped.value

    @property
    def peak_active_tokens(self) -> int:
        """Max over ticks of sum(active lengths)."""
        return self._g_peak_active.value

    @property
    def peak_kv_used_tokens(self) -> int:
        """Max over ticks of resident KV tokens."""
        return self._g_peak_kv.value

    def _prefix_hit(self, req: Request):
        """Longest indexed full-page prefix of the teacher-forced tokens,
        trimmed to the chunk grid (DESIGN.md §11).

        The resume cursor must land on the same chunk boundaries a cold
        prefill would use: every prefill starts at position 0 and absorbs
        ``chunk_size`` tokens per tick, so all KV content — and the blocked
        softmax tilings that shape ExpMul's float accumulation — lives on
        one canonical grid. Aligning the cursor down keeps the remaining
        warm chunks bit-identical to the cold run's, which is what makes
        warm temp-0 streams equal cold ones for *every* variant. The cursor
        is also capped at len-1 so at least one position remains to produce
        the first sampled token's logits. Kept blocks are trimmed to those
        covering the cursor — a block straddling the cursor stays spliced
        (its head rows are live context) and triggers COW on first write.

        Returns (blocks_to_splice, cursor_tokens).
        """
        blocks = self.pool.match_prefix(req.prefill_toks)
        if not blocks:
            return [], 0
        grid = self.chunk_size if self.chunk_size > 1 else 1
        cursor = min(len(blocks) * self.page_size, len(req.prefill_toks) - 1)
        cursor = cursor // grid * grid
        if cursor <= 0:
            return [], 0
        return blocks[:blocks_for(cursor, self.page_size)], cursor

    def _admit(self):
        for s in range(self.slots):
            if self.requests[s] is None and self.queue:
                req = self.queue[0]
                if fault_point("admission", rid=req.rid, slot=s):
                    # dropped admission (chaos): the head stays queued and
                    # is retried next tick — a delay-only fault, so temp-0
                    # streams are unchanged (scheduling-invariant keys)
                    break
                hit_blocks, cursor = ([], 0)
                if self.prefix_cache:
                    hit_blocks, cursor = self._prefix_hit(req)
                take = (min(self.chunk_size, len(req.prefill_toks) - cursor)
                        if self.chunk_size > 1 else 1)
                if self.paged and not self.pool.can_admit(
                        hit_blocks, cursor + take):
                    if self.pool.used_blocks == 0 and not any(
                            r is not None for r in self.requests):
                        # an idle pool can't hold even the first chunk:
                        # waiting will never help — fail like _reserve does
                        # (a hit never hurts admissibility: spliced blocks
                        # cover at least the capacity they pin)
                        raise RuntimeError(
                            f"KV pool too small: request {req.rid} "
                            f"needs {cursor + take} tokens "
                            f"for its first chunk but the whole pool holds "
                            f"{self.pool.pool_blocks * self.page_size}; "
                            f"raise pool_blocks")
                    break  # pool too tight right now; retry as blocks free
                self.queue.pop(0)
                if req.admit_order < 0:
                    # seniority is assigned once and survives preemption:
                    # a requeued request must outrank later arrivals, or two
                    # requests that don't fit together evict each other
                    # forever (oldest-first reservation + youngest victim)
                    req.admit_order = self._admit_seq
                    self._admit_seq += 1
                self.requests[s] = req
                if req.admit_step is None:
                    req.admit_step = self.ticks
                    req.admit_time = self._now
                    if req.deadline_steps is not None:
                        # the step budget starts at first admission (queue
                        # wait is covered by the wall-clock budget, which
                        # was armed at submit)
                        self.deadlines.arm(req.rid,
                                           step_budget=req.deadline_steps,
                                           step_base=self.ticks)
                    if self.metrics.trace:
                        self.metrics.name_track(PID_REQUESTS, req.rid,
                                                f"req {req.rid}")
                        self.metrics.begin(
                            f"req {req.rid}", pid=PID_REQUESTS, tid=req.rid,
                            step=self.ticks, prompt=len(req.prompt),
                            max_new=req.max_new)
                elif self.metrics.trace:
                    self.metrics.instant(
                        "resume", pid=PID_REQUESTS, tid=req.rid,
                        step=self.ticks,
                        recompute=len(req.prefill_toks) - len(req.prompt))
                if hit_blocks:
                    self.pool.splice(s, hit_blocks)
                    req.prefix_hit += cursor
                    self._c_hit_tokens.inc(cursor)
                    self._c_flops_skipped.inc(
                        analytic_prefill_flops(self.cfg, 0, cursor))
                    if self.metrics.trace:
                        self.metrics.instant(
                            "prefix_splice", pid=PID_REQUESTS, tid=req.rid,
                            step=self.ticks, hit_tokens=cursor,
                            blocks=len(hit_blocks))
                req.registered_blocks = len(hit_blocks)
                req.pos = cursor
                self.lengths[s] = cursor
                self.cur_tok[s] = req.prefill_toks[cursor]
                # NOTE: slot state is logically reset via lengths (the
                # attention mask hides stale cache rows); recurrent-state
                # archs need a true reset, handled by zeroing below.
                self._reset_slot_state(s)

    def _reset_slot_state(self, s):
        """Zero recurrent per-slot state on admission. Only recurrent-kind
        caches are touched: attention caches are masked by lengths (and in
        paged mode their second axis is physical pool rows, not slots)."""
        recurrent = [i for i, k in enumerate(self.cfg.block_pattern)
                     if k in ("rglru", "mlstm", "slstm")]
        if not recurrent:
            return
        caches = list(self.state["caches"])
        for i in recurrent:
            caches[i] = jax.tree.map(lambda l: l.at[:, s].set(0), caches[i])
        state = dict(self.state)
        state["caches"] = tuple(caches)
        self.state = state

    def _finish_or_continue(self, s, tok):
        """Record a sampled token for slot s; free the slot when done.

        TTFT/TPOT are recorded here: TTFT in engine steps uses the bench
        convention (first_token_step - admit_step + 1, admission ->
        first sample inclusive); TPOT is the step gap between consecutive
        samples of one request — honest about stalls, since the gap of the
        first sample after a preemption spans the whole requeue + resume
        period. The ms twins reuse the per-tick host timestamp (one
        ``perf_counter`` per step, so values are quantized to tick starts
        — no extra timestamps or device syncs on the token path); ms TTFT
        runs from ``submit()``, so it counts the wait in the queue."""
        req = self.requests[s]
        if req.first_token_step is None:
            req.first_token_step = self.ticks
            self._h_ttft_steps.record(req.first_token_step
                                      - req.admit_step + 1)
            if req.submit_time is not None:
                self._h_ttft_ms.record((self._now - req.submit_time) * 1e3)
            if self.metrics.trace:
                self.metrics.instant("first_token", pid=PID_REQUESTS,
                                     tid=req.rid, step=self.ticks)
        else:
            self._h_tpot_steps.record(self.ticks - req.last_token_step)
            if req.last_token_time is not None:
                self._h_tpot_ms.record(
                    (self._now - req.last_token_time) * 1e3)
        req.last_token_step = self.ticks
        req.last_token_time = self._now
        req.out.append(tok)
        self.cur_tok[s] = tok
        self._c_generated.inc()
        if len(req.out) >= req.max_new or self.lengths[s] >= self.max_len - 1:
            self._release_slot(s, "length")

    # -- paged capacity management ------------------------------------------
    def _preempt(self, s):
        """Evict slot s and requeue its request for recompute-resumption.

        With ``max_preemptions`` set, a request that has already been
        evicted that many times finishes with
        ``finish_reason="preempt_limit"`` instead of thrashing the pool
        forever — its blocks free all the same, so the caller's capacity
        retry proceeds."""
        req = self.requests[s]
        if (self.max_preemptions is not None
                and req.preemptions >= self.max_preemptions):
            self._release_slot(s, "preempt_limit")
            return
        if self.prefix_cache:
            # index the victim's completed pages first: they land in the
            # cached tier, so unless the preemptor reclaims them too the
            # victim resumes via a prefix hit instead of recompute
            self._register_full_pages(s, req)
        self.pool.evict_slot(s)
        self.requests[s] = None
        self.lengths[s] = 0
        req.prefill_toks = list(req.prompt) + list(req.out)
        req.pos = 0
        req.preemptions += 1
        self._c_preemptions.inc()
        if self.metrics.trace:
            self.metrics.instant("preempt", pid=PID_REQUESTS, tid=req.rid,
                                 step=self.ticks,
                                 tokens=len(req.prefill_toks))
        self.queue.insert(0, req)  # resumes as soon as space frees up
        self._g_queue.set(len(self.queue))

    def _pick_victim(self, exclude):
        """Youngest active request (latest admitted) other than ``exclude``."""
        best = None
        for s in range(self.slots):
            if s == exclude or self.requests[s] is None:
                continue
            if best is None or (self.requests[s].admit_order
                                > self.requests[best].admit_order):
                best = s
        return best

    def _take_for(self, s) -> int:
        req = self.requests[s]
        if self.chunk_size > 1 and req.pos < len(req.prefill_toks):
            return min(self.chunk_size, len(req.prefill_toks) - req.pos)
        return 1

    def _cow_shared_tail(self, s):
        """Copy-on-write before this tick's writes to slot ``s`` (§11).

        Writes are append-only at ``lengths[s]``; the only block that can be
        both shared and write-targeted is the one straddling a mid-page
        write cursor — a spliced hit block whose tail rows this slot is
        about to overwrite (fresh blocks are private by construction, and a
        block this slot registered is fully written, never written again).
        The pool hands out a private replacement id and this method performs
        the device page copy; the original keeps its index entry and any
        other references."""
        off = int(self.lengths[s])
        if off % self.page_size == 0:
            return
        idx = off // self.page_size
        if not self.pool.is_shared(int(self.pool.tables[s, idx])):
            return
        while True:
            pair = self.pool.cow_block(s, idx)
            if pair is not None:
                break
            victim = self._pick_victim(exclude=s)
            if victim is None:
                raise RuntimeError(
                    f"KV pool exhausted: slot {s} needs a copy-on-write "
                    f"block (pool={self.pool.pool_blocks}) with no one "
                    f"left to evict; raise pool_blocks")
            self._preempt(victim)
        src, dst = pair
        self.state = self._cow_copy(self.state, src, dst)
        if self.metrics.trace:
            self.metrics.instant("cow_copy", pid=PID_REQUESTS,
                                 tid=self.requests[s].rid, step=self.ticks,
                                 src=src, dst=dst)

    def _reserve(self, active):
        """Grow block tables to cover this tick's writes, oldest request
        first; preempt youngest-first when the pool is exhausted (the pool
        itself reclaims cached-LRU blocks before any preemption — §11
        eviction ordering). Returns the surviving active slots."""
        for s in sorted(active, key=lambda s: self.requests[s].admit_order):
            if self.requests[s] is None:
                continue  # preempted by an older request's reservation
            if self.prefix_cache:
                self._cow_shared_tail(s)
            if self.requests[s] is None:
                continue
            target = int(self.lengths[s]) + self._take_for(s)
            while not self.pool.alloc(s, target):
                victim = self._pick_victim(exclude=s)
                if victim is None:
                    raise RuntimeError(
                        f"KV pool exhausted: slot {s} needs {target} tokens "
                        f"({blocks_for(target, self.page_size)} blocks, "
                        f"pool={self.pool.pool_blocks}) with no one left "
                        f"to evict; raise pool_blocks")
                self._preempt(victim)
        return [s for s in range(self.slots) if self.requests[s] is not None]

    def _register_full_pages(self, s, req: Request):
        """Index newly completed full pages of slot ``s`` for future prefix
        hits (§11). Page i's chain key is (physical id of page i-1, its ps
        tokens), so the key transitively covers the whole prefix — which is
        exactly what the KV content of the page depends on. The logical
        token at position p is always (prompt + out)[p]: after a preemption
        ``prefill_toks`` is prompt + out-so-far and sampling keeps appending
        to ``out``, so the concatenation stays the written sequence."""
        ps = self.page_size
        full = int(self.lengths[s]) // ps
        if full <= req.registered_blocks:
            return
        seq = req.prompt + req.out
        for i in range(req.registered_blocks, full):
            parent = int(self.pool.tables[s, i - 1]) if i else -1
            self.pool.register_block(int(self.pool.tables[s, i]), parent,
                                     seq[i * ps:(i + 1) * ps])
        req.registered_blocks = full

    # -- engine steps -------------------------------------------------------
    def _block_tables(self):
        return jnp.asarray(self.pool.tables)

    def _sample_keys(self):
        """Per-slot sampling keys: fold (admission seniority, #generated)
        into the engine seed, so a request's temp>0 stream is a function of
        its own history — invariant to tick interleaving, and hence to
        prefix-cache hits or preemptions changing the schedule. At temp 0
        sampling is argmax and the keys are inert."""
        keys = [
            self.key if req is None else jax.random.fold_in(
                jax.random.fold_in(self.key, req.admit_order), len(req.out))
            for req in self.requests
        ]
        return jnp.stack(keys)

    def _register_active_pages(self):
        for s in range(self.slots):
            if self.requests[s] is not None:
                self._register_full_pages(s, self.requests[s])

    # -- fault paths (DESIGN.md §13) -----------------------------------------
    def _corrupt_kv(self, s):
        """kv_corrupt chaos: poison the last physical page holding slot
        ``s``'s resident KV (non-finite floats / sentinel ints via
        ``models.api.poison_paged_block``). The slot's very next attention
        reads the page, and masked rows still propagate — a masked score
        is -inf, softmax gives it weight 0, and 0·NaN = NaN in p@V — so
        the corruption surfaces as non-finite logits for this slot on the
        same tick, which is what the quarantine sentinel must catch."""
        idx = max(0, (int(self.lengths[s]) - 1) // self.page_size)
        block = int(self.pool.tables[s, idx])
        if self._poison is None:
            ps = self.page_size
            self._poison = jax.jit(
                lambda state, b: poison_paged_block(
                    state, self.cfg, b, page_size=ps))
        self.state = self._poison(self.state, jnp.int32(block))

    def _scrub_slot(self, s):
        """Zero the physical pages a quarantined slot solely owns before
        they rejoin the free list. Stale *finite* garbage in a freed page
        is harmless — masked rows get softmax weight 0 — but a NaN row
        survives the mask (0·NaN = NaN in p@V), so a recirculated
        poisoned page would corrupt its next owner's logits mid-page.
        Shared pages (refcount > 1) are skipped: another live reference
        holds valid content there and this slot never wrote them."""
        if self._scrub is None:
            ps = self.page_size
            self._scrub = jax.jit(
                lambda state, b: poison_paged_block(
                    state, self.cfg, b, page_size=ps, value=0))
        for i in range(int(self.pool.n_blocks[s])):
            b = int(self.pool.tables[s, i])
            if int(self.pool.refcount[b]) == 1:
                self.state = self._scrub(self.state, jnp.int32(b))

    def _chaos_logits(self, active, logits):
        """logits chaos: overwrite an injected slot's logits row with NaN
        before sampling (models a device-side numerical fault)."""
        for s in active:
            if fault_point("logits", slot=s, rid=self.requests[s].rid):
                logits = jnp.asarray(logits).at[s].set(jnp.nan)
        return logits

    def _guard_nonfinite(self, active, logits, nxt):
        """Host-side NaN/Inf sentinel (§13): one vectorized finiteness
        reduction over the tick's logits plus a range check on the sampled
        tokens — no extra device work beyond the per-tick host transfer
        the engine already performs. Only *active* slots are judged: idle
        slots run fully-masked rows whose logits are legitimately
        non-finite. Faulted requests are quarantined (``"failed"``, blocks
        freed and de-indexed) or, under ``nan_guard="strict"``, raise
        ``NonFiniteLogitsError``. Returns the surviving active slots."""
        if self.nan_guard == "off":
            return active
        finite = np.asarray(jnp.all(jnp.isfinite(logits), axis=-1))
        vocab = self.cfg.vocab_size
        survivors = []
        for s in active:
            tok = int(nxt[s])
            if bool(finite[s]) and 0 <= tok < vocab:
                survivors.append(s)
                continue
            req = self.requests[s]
            if self.nan_guard == "strict":
                raise NonFiniteLogitsError(
                    f"non-finite logits for request {req.rid} (slot {s}) "
                    f"at engine step {self.ticks}")
            logger.warning(
                "quarantining request %d (slot %d): non-finite logits at "
                "engine step %d after %d generated tokens", req.rid, s,
                self.ticks, len(req.out))
            self._release_slot(s, "failed")
        return survivors

    def _chunk_inputs(self, active):
        """Host arrays of one chunked step: prefilling slots absorb up to
        chunk_size prompt tokens; decode-ready slots ride along as 1-valid
        chunks."""
        C = self.chunk_size
        toks = np.zeros((self.slots, C), np.int32)
        nv = np.zeros((self.slots,), np.int32)
        for s in active:
            req = self.requests[s]
            if req.pos < len(req.prefill_toks):
                take = min(C, len(req.prefill_toks) - req.pos)
                toks[s, :take] = req.prefill_toks[req.pos:req.pos + take]
            else:
                take = 1
                toks[s, 0] = self.cur_tok[s]
            nv[s] = take
        return toks, nv

    def _step(self, active, prefilling: bool):
        """One engine step over the ``active`` slots: the chunked prefill
        graph when ``prefilling``, else the single-token decode graph
        (which, with chunk_size=1, also teacher-forces prompts). Each
        phase is a span (DESIGN.md §12), and none adds a device sync: the
        only host waits are ``serve.wait``'s pull of the sampled tokens
        and the guard's finiteness pull."""
        m = self.metrics
        with m.span("serve.inputs"):
            if prefilling:
                toks, nv = self._chunk_inputs(active)
                args = (self.params, self.state, jnp.asarray(toks),
                        jnp.asarray(self.lengths), jnp.asarray(nv))
            else:
                args = (self.params, self.state,
                        jnp.asarray(self.cur_tok), jnp.asarray(self.lengths))
            if self.paged:
                args += (self._block_tables(),)
        kind = "prefill" if prefilling else "decode"
        with m.span("serve.dispatch." + kind, active=len(active)):
            logits, self.state = (self._prefill if prefilling
                                  else self._decode)(*args)
        with m.span("serve.sample"):
            logits = self._chaos_logits(active, logits)
            nxt = sample_tokens(self._sample_keys(), logits,
                                temperature=self.temperature)
        with m.span("serve.wait"):
            nxt = np.asarray(nxt)
        self._c_ticks.inc()
        (self._c_prefill_steps if prefilling else self._c_decode_steps).inc()
        # sentinel before bookkeeping: a quarantined slot contributes no
        # length/token updates, so survivors see the same schedule a
        # fault-free run would (minus the freed capacity)
        with m.span("serve.guard"):
            active = self._guard_nonfinite(active, logits, nxt)
        with m.span("serve.bookkeep"):
            if prefilling:
                self._advance_prefill(active, nv, nxt)
            else:
                self._advance_decode(active, nxt)
            if self.prefix_cache:
                # index pages completed by this tick's writes (finished
                # slots already registered theirs in _finish_or_continue)
                self._register_active_pages()
            self._track_memory(
                [s for s in range(self.slots) if self.requests[s] is not None])

    def _advance_prefill(self, active, nv, nxt):
        for s in active:
            req = self.requests[s]
            take = int(nv[s])
            self.lengths[s] += take
            if req.pos < len(req.prefill_toks):  # was prefilling this step
                n_prompt = len(req.prompt)
                recompute = max(0, min(req.pos + take, len(req.prefill_toks))
                                - max(req.pos, n_prompt))
                req.pos += take
                req.prefill_kv_bytes += take * self.token_bytes
                self._c_prompt_tokens.inc(take - recompute)
                self._c_recompute.inc(recompute)
                if req.pos < len(req.prefill_toks):
                    continue                    # still mid-prompt: no sample
            self._finish_or_continue(s, int(nxt[s]))

    def _advance_decode(self, active, nxt):
        for s in active:
            req = self.requests[s]
            if self.lengths[s] < len(req.prefill_toks):
                # the token written this tick was a prompt token (counted
                # pre-increment so prompt[0] is included, matching prefill)
                if self.lengths[s] < len(req.prompt):
                    self._c_prompt_tokens.inc()
                else:
                    self._c_recompute.inc()
                req.prefill_kv_bytes += self.token_bytes
            self.lengths[s] += 1
            req.pos = max(req.pos, int(self.lengths[s]))
            pos = int(self.lengths[s])
            if pos < len(req.prefill_toks):     # teacher-forcing (chunk=1)
                self.cur_tok[s] = req.prefill_toks[pos]
            else:
                self._finish_or_continue(s, int(nxt[s]))

    def _track_memory(self, active):
        self._g_peak_active.set_max(
            int(sum(self.lengths[s] for s in active)))
        used = (self.pool.used_blocks * self.page_size if self.paged
                else self.slots * self.max_len)
        self._g_peak_kv.set_max(int(used))

    def tick(self):
        """Advance the engine by one step (prefill or decode). The whole
        call is one ``serve.tick`` span; ``active`` counts the slots held
        when it starts."""
        with self.metrics.span("serve.tick", step=self.ticks + 1,
                               active=self.slots - self.requests.count(None)):
            return self._tick()

    def _tick(self):
        m = self.metrics
        self._now = time.perf_counter()
        with m.span("serve.admit"):
            # deadline sweep first: an expired request must not be
            # admitted, reserved for, or stepped this tick
            self._expire_deadlines()
            self._admit()
            self._g_queue.set(len(self.queue))
        active = [s for s in range(self.slots) if self.requests[s] is not None]
        if not active:
            return False
        if self.paged:
            with m.span("serve.reserve"):
                # forced-preemption chaos (§13): preemption is
                # stream-preserving by the §7 recompute argument, so an
                # injected storm must leave every temp-0 token stream
                # bit-identical — only slower
                for s in active:
                    if (self.requests[s] is not None and fault_point(
                            "preempt", slot=s, rid=self.requests[s].rid)):
                        self._preempt(s)
                active = [s for s in active if self.requests[s] is not None]
                active = self._reserve(active)
                # kv-corruption chaos after reservation, so the poisoned
                # physical block id is the one this tick actually attends
                # over
                for s in active:
                    if fault_point("kv_corrupt", slot=s,
                                   rid=self.requests[s].rid):
                        self._corrupt_kv(s)
            if not active:
                return bool(self.queue)
        prefilling = self.chunk_size > 1 and any(
            self.requests[s].pos < len(self.requests[s].prefill_toks)
            for s in active
        )
        self._step(active, prefilling)
        return True

    def run(self, max_steps: int | None = None):
        """Tick until every request reaches a terminal state. ``max_steps``
        bounds the drive loop (a chaos run with an unbounded admission-drop
        rate could otherwise spin on an unadmittable queue forever); the
        per-request safety net is ``deadline_steps``/``deadline_s``."""
        steps = 0
        while self.tick() or self.queue:
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break

    def save_snapshot(self, path: str) -> dict:
        """Crash-consistent snapshot of the whole engine (DESIGN.md §13):
        device state, pool + radix index, live/queued requests, deadline
        budgets, and metrics, written atomically. Call between ticks.
        ``serve.snapshot.restore_engine(path, params, cfg)`` rebuilds."""
        from repro.serve.snapshot import save_snapshot
        return save_snapshot(self, path)

    # -- observability surfaces (DESIGN.md §12) ------------------------------
    def metrics_snapshot(self) -> dict:
        """Everything observable about this engine as one JSON-able dict:
        the registry dump (counters/gauges/histograms — the per-spec
        ``attention_dispatch_total`` family included), TTFT/TPOT
        percentile conveniences (engine steps — the scheduling-level
        latency signal), and ``memory_stats()``."""
        snap = self.metrics.snapshot()
        snap["ttft_steps_p50"] = self._h_ttft_steps.quantile(0.50)
        snap["ttft_steps_p99"] = self._h_ttft_steps.quantile(0.99)
        snap["tpot_steps_p50"] = self._h_tpot_steps.quantile(0.50)
        snap["tpot_steps_p99"] = self._h_tpot_steps.quantile(0.99)
        snap["memory"] = self.memory_stats()
        # terminal-state accounting (§13): every finished request counted
        # under exactly one reason; quarantines called out separately
        snap["finish_reasons"] = {
            reason: c.value for reason, c in self._c_reason.items()}
        snap["quarantined"] = self._c_quarantined.value
        return snap

    # -- memory accounting (BENCH_serve.json) -------------------------------
    def kv_reserved_tokens(self) -> int:
        """KV token rows reserved up front (per attention layer)."""
        if self.paged:
            return self.pool.pool_blocks * self.page_size
        return self.slots * self.max_len

    def memory_stats(self) -> dict:
        st = {
            "kv_layout": self.kv_layout,
            "kv_dtype": self.kv_dtype,
            "kv_token_bytes": int(self.token_bytes),
            "kv_reserved_tokens": int(self.kv_reserved_tokens()),
            "kv_peak_used_tokens": int(self.peak_kv_used_tokens),
            "kv_peak_active_tokens": int(self.peak_active_tokens),
            "kv_tokens_per_active_token": (
                self.peak_kv_used_tokens / self.peak_active_tokens
                if self.peak_active_tokens else 0.0),
            # real bytes (codes + scale pools): the cross-dtype comparison
            "kv_reserved_bytes": int(self.kv_reserved_tokens()
                                     * self.token_bytes),
            "kv_peak_used_bytes": int(self.peak_kv_used_tokens
                                      * self.token_bytes),
            "kv_bytes_per_active_token": (
                self.peak_kv_used_tokens * self.token_bytes
                / self.peak_active_tokens
                if self.peak_active_tokens else 0.0),
            "preemptions": int(self.preemptions),
            "recompute_tokens": int(self.recompute_tokens),
        }
        if self.paged:
            st["page_size"] = self.page_size
            st["pool_blocks"] = self.pool.pool_blocks
            st["evictions"] = self.pool.stats.evictions
            st["alloc_failures"] = self.pool.stats.alloc_failures
            # cache residency split (§11): used = referenced by a live slot,
            # cached = unreferenced-but-retained prefix pages, free = blank.
            # used_bytes above deliberately exclude the cached tier.
            st["prefix_cache"] = self.prefix_cache
            st["kv_used_blocks"] = int(self.pool.used_blocks)
            st["kv_cached_blocks"] = int(self.pool.cached_block_count)
            st["kv_free_blocks"] = int(self.pool.free_block_count)
            st["kv_cached_tokens"] = int(self.pool.cached_block_count
                                         * self.page_size)
            st["kv_cached_bytes"] = int(self.pool.cached_bytes)
            if self.prefix_cache:
                ps = self.pool.stats
                st["cache_lookups"] = ps.cache_lookups
                st["cache_hits"] = ps.cache_hits
                st["hit_blocks"] = ps.hit_blocks
                st["cow_copies"] = ps.cow_copies
                st["cached_evictions"] = ps.cached_evictions
                st["prefix_hit_tokens"] = int(self.prefix_hit_tokens)
                st["prefill_flops_skipped"] = int(self.prefill_flops_skipped)
        return st
