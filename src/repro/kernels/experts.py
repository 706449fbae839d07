"""Grouped expert matmul for dropless MoE serving (DESIGN.md §14).

The routed rows of a batch arrive sorted by expert, ``group_sizes[e]`` rows
for held expert ``e`` one after another; ``expert_gmm`` multiplies each
group by its own expert's weight in one Pallas TPU kernel (the grouped
matmul JAX ships as ``megablox.gmm``), visiting only the row tiles that hold
rows, so an expert's weight is read once per tile its rows touch and no row
is dropped or padded to a capacity. Rows past ``sum(group_sizes)`` are
never written: callers mask them.

The kernel runs under its own jit so that the device trace names it
``expert_gmm`` (the benchmark's ``expert_gmm_roofline`` reader finds it by
that name). On CPU it runs in Pallas interpret mode.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the un-jitted grouped matmul: called inside ``expert_gmm``'s own jit, so
# the Mosaic call carries this module's name rather than ``gmm``
_gmm = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm").gmm.__wrapped__

def row_tile(m: int) -> int:
    """Rows per tile for ``m`` routed rows: 128, or all of them (rounded up
    to 16, a bfloat16 sublane tile) when fewer."""
    return 128 if m >= 128 else -(-m // 16) * 16


def _tile(dim: int) -> int:
    """A contraction or output tile: 512 where it divides, else whole."""
    return 512 if dim % 512 == 0 else dim


@functools.partial(jax.jit, static_argnames=("interpret",))
def expert_gmm(lhs, rhs, group_sizes, *, interpret=False):
    """lhs (m, k) rows sorted by group; rhs (E, k, n); group_sizes (E,)
    int32 -> (m, n) float32, row r of group e being ``lhs[r] @ rhs[e]``.
    ``m`` must be a multiple of ``row_tile(m)``."""
    m, k = lhs.shape
    n = rhs.shape[2]
    return _gmm(lhs, rhs, group_sizes, preferred_element_type=jnp.float32,
                tiling=(row_tile(m), _tile(k), _tile(n)),
                interpret=interpret)


def grouped_ffn(xs, params, group_sizes, act, interpret=None):
    """The gated expert FFN of sorted rows ``xs`` (m, d) through the held
    experts' ``w_gate``/``w_up`` (E, d, f) and ``w_down`` (E, f, d), gate
    activation ``act`` -> (m, d) float32; rows past ``sum(group_sizes)``
    are garbage."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    gmm = functools.partial(expert_gmm, group_sizes=group_sizes,
                            interpret=interpret)
    h = act(gmm(xs, params["w_gate"])) * gmm(xs, params["w_up"])
    return gmm(h.astype(xs.dtype), params["w_down"])
