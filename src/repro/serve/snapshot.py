"""Crash-consistent engine snapshot/restore (DESIGN.md §13).

``save_snapshot(engine, path)`` serializes a quiesced-between-ticks
``ServeEngine`` — device KV/recurrent state, the paged ``BlockPool``
(tables, refcounts, free list, radix index, cached tier), every live
request (in-slot and queued, preserving slot assignment and queue order),
per-request deadline budgets, the admission sequence / rid allocator, and
the full metrics registry — into a single ``.npz`` written atomically
(tmp file + ``os.replace``), so a crash mid-save can never leave a
half-written snapshot: readers see the old file or the new one.

``restore_engine(path, params, cfg)`` rebuilds an identically shaped
engine from the snapshot's recorded constructor kwargs (so pool geometry
and compiled-graph shapes match by construction), then overlays the
serialized state. The guarantees the tests pin down:

  * mid-flight temp-0 requests continue bit-identically to an engine
    that never stopped: the teacher-forced resumption state (``pos``,
    ``prefill_toks``, ``out``), the per-slot device caches, and the
    block tables all round-trip exactly, and temp>0 streams survive too
    because sampling keys are a pure function of (seed, admit_order,
    len(out)) — all serialized.
  * the cached prefix tier survives: the radix index and cached block
    contents round-trip, so a warm prompt re-submitted after restore
    splices its prefix without re-prefilling (the bench gates
    warm-after-restore TTFT at <= 25% of cold).
  * metrics continuity: counters/gauges/histograms resume from their
    snapshot values (the engine-step clock included, which keeps
    step-based deadline bases valid). Wall-clock quantities do not
    cross processes: request timestamps restore as ``None`` (the ms
    TTFT/TPOT histograms honestly skip them) and wall-clock deadline
    budgets are re-armed in full against the restore-time clock.

Weights are deliberately not serialized: ``params``/``cfg`` come from the
caller's checkpoint pipeline, and restore validates the architecture
fingerprint (config name + state-leaf shapes/dtypes) loudly instead of
silently reinterpreting a mismatched cache.

Snapshots must be taken between ticks (the engine mutates state only
inside ``tick()``); ``ServeEngine.save_snapshot`` is the convenience
wrapper. Device arrays are stored as raw little-endian bytes with dtype
strings in the JSON header, which keeps ml_dtypes leaves (bfloat16, fp8)
out of numpy's pickle path.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

SNAPSHOT_VERSION = 1

# Request fields that are host wall-clock timestamps: perf_counter bases
# are meaningless in another process, so they restore as None and every
# consumer (ms histograms, deadline re-arming) handles that honestly.
_TIME_FIELDS = ("submit_time", "admit_time", "last_token_time")


def _np_default(o):
    """JSON fallback for numpy scalars (token lists routinely carry
    np.int64 elements straight from callers' rngs)."""
    if isinstance(o, np.integer):
        return int(o)
    if isinstance(o, np.floating):
        return float(o)
    raise TypeError(f"not JSON serializable: {type(o).__name__}")


def _request_to_json(req) -> dict:
    import dataclasses
    d = dataclasses.asdict(req)
    for f in _TIME_FIELDS:
        d[f] = None
    return d


def _request_from_json(d):
    from repro.serve.engine import Request
    return Request(**d)


def save_snapshot(engine, path: str) -> dict:
    """Write a crash-consistent snapshot of ``engine`` to ``path``.

    Returns the JSON-able meta header (useful for logging/benching).
    Must be called between ticks — never from inside a tick. The host
    copies of the state are dropped before it returns: on a CPU they are
    views of the device buffers, and a view left alive would keep the next
    tick from donating the state (DESIGN.md §6).
    """
    leaves, _ = jax.tree.flatten(engine.state)
    host = [np.asarray(x) for x in leaves]
    try:
        keydata = np.asarray(jax.random.key_data(engine.key))
        key_typed = True
    except TypeError:
        keydata = np.asarray(engine.key)
        key_typed = False
    live = []
    for s, req in enumerate(engine.requests):
        if req is not None:
            ent = _request_to_json(req)
            ent["_slot"] = s
            live.append(ent)
    queued = [_request_to_json(r) for r in engine.queue]
    deadlines = {}
    for req in list(engine.requests) + list(engine.queue):
        if req is None:
            continue
        ent = engine.deadlines._armed.get(req.rid)
        if ent is not None:
            # [step_budget, step_base, wall_budget]: the step base stays
            # absolute (the engine-step counter round-trips through the
            # metrics dump); the wall budget re-arms in full at restore
            deadlines[str(req.rid)] = [ent[0], ent[1], ent[2]]
    meta = {
        "version": SNAPSHOT_VERSION,
        "cfg_name": engine.cfg.name,
        "ctor": dict(engine._ctor),
        "n_leaves": len(host),
        "leaves": [{"dtype": str(x.dtype), "shape": list(x.shape)}
                   for x in host],
        "key_typed": key_typed,
        "key_dtype": str(keydata.dtype),
        "key_shape": list(keydata.shape),
        "paged": engine.paged,
        "requests": live,
        "queue": queued,
        "deadlines": deadlines,
        "admit_seq": engine._admit_seq,
        "next_rid": engine._next_rid,
        "rids": sorted(engine._rids),
        "pool": engine.pool.dump_state() if engine.paged else None,
        "metrics": engine.metrics.dump_values(),
    }
    entries = {"meta": np.asarray(json.dumps(meta, default=_np_default))}
    for i, x in enumerate(host):
        # raw bytes keep ml_dtypes leaves (bfloat16/fp8) off numpy's
        # pickle path; dtype+shape live in the JSON header
        entries[f"leaf_{i}"] = np.ascontiguousarray(x).view(np.uint8)
    entries["key"] = np.ascontiguousarray(keydata).view(np.uint8)
    entries["lengths"] = np.asarray(engine.lengths)
    entries["cur_tok"] = np.asarray(engine.cur_tok)
    tmp = f"{path}.tmp.{os.getpid()}"
    try:
        with open(tmp, "wb") as f:
            np.savez(f, **entries)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)  # atomic on POSIX: old file or new, never half
    finally:
        if os.path.exists(tmp):
            os.remove(tmp)
    return meta


def _view_back(raw: np.ndarray, dtype: str, shape: list) -> np.ndarray:
    return raw.view(np.dtype(dtype)).reshape(shape)


def restore_engine(path: str, params, cfg, *, metrics=None, trace=False):
    """Rebuild a ``ServeEngine`` from a snapshot written by
    ``save_snapshot``. ``params``/``cfg`` must be the same checkpoint the
    snapshotting engine served — the architecture fingerprint is
    validated and a mismatch raises ``ValueError`` (a mismatched cache
    silently reinterpreted would be a correctness bug, not a restart)."""
    import time

    import jax.numpy as jnp

    from repro.serve.engine import ServeEngine

    with np.load(path, allow_pickle=False) as z:
        meta = json.loads(str(z["meta"][()]))
        if meta.get("version") != SNAPSHOT_VERSION:
            raise ValueError(
                f"snapshot {path!r} has version {meta.get('version')!r}; "
                f"this build reads version {SNAPSHOT_VERSION}")
        if meta["cfg_name"] != cfg.name:
            raise ValueError(
                f"snapshot {path!r} was taken from config "
                f"{meta['cfg_name']!r} but restore got {cfg.name!r}; "
                f"pass the matching checkpoint")
        raw_leaves = [np.asarray(z[f"leaf_{i}"])
                      for i in range(meta["n_leaves"])]
        raw_key = np.asarray(z["key"])
        lengths = np.asarray(z["lengths"])
        cur_tok = np.asarray(z["cur_tok"])

    engine = ServeEngine(params, cfg, metrics=metrics, trace=trace,
                         **meta["ctor"])
    fresh, treedef = jax.tree.flatten(engine.state)
    if len(fresh) != meta["n_leaves"]:
        raise ValueError(
            f"snapshot {path!r} carries {meta['n_leaves']} state leaves "
            f"but {cfg.name!r} builds {len(fresh)}; config/checkpoint "
            f"mismatch")
    leaves = []
    for i, (ref, raw, spec) in enumerate(zip(fresh, raw_leaves,
                                             meta["leaves"])):
        got = _view_back(raw, spec["dtype"], spec["shape"])
        if (tuple(got.shape) != tuple(ref.shape)
                or str(got.dtype) != str(ref.dtype)):
            raise ValueError(
                f"snapshot leaf {i} is {spec['dtype']}{spec['shape']} but "
                f"the rebuilt engine expects {ref.dtype}{list(ref.shape)}; "
                f"config/checkpoint mismatch")
        leaves.append(got)
    # free the rebuilt engine's empty pool before placing the snapshot's:
    # the device holds one state at a time (DESIGN.md §6)
    for ref in fresh:
        ref.delete()
    engine.state = jax.tree.unflatten(treedef,
                                      [jnp.asarray(x) for x in leaves])

    keydata = _view_back(raw_key, meta["key_dtype"], meta["key_shape"])
    engine.key = (jax.random.wrap_key_data(jnp.asarray(keydata))
                  if meta["key_typed"] else jnp.asarray(keydata))
    engine.lengths[:] = lengths
    engine.cur_tok[:] = cur_tok
    if meta["paged"]:
        engine.pool.load_state(meta["pool"])
    engine.metrics.load_values(meta["metrics"])
    engine._admit_seq = int(meta["admit_seq"])
    engine._next_rid = int(meta["next_rid"])
    engine._rids = set(meta["rids"])
    for ent in meta["requests"]:
        s = ent.pop("_slot")
        engine.requests[s] = _request_from_json(ent)
    engine.queue = [_request_from_json(d) for d in meta["queue"]]
    now = time.perf_counter()
    for rid_s, (sb, s0, wb) in meta["deadlines"].items():
        rid = int(rid_s)
        if sb is not None:
            engine.deadlines.arm(rid, step_budget=sb, step_base=s0)
        if wb is not None:
            # wall budgets restart in full against this process's clock:
            # generous, but honest — elapsed wall time in a dead process
            # is not recoverable, and a tighter guess would expire
            # requests that were inside budget at the crash
            engine.deadlines.arm(rid, wall_budget=wb, wall_base=now)
    return engine
