"""What the benchmark's weights and the system under test share across
architectures.

The benchmark makes its own weights: one jitted call draws them on the
device, in the dtype they are served in, in a plain layout of the
architecture's own (``bench/archs/<model_type>.py``: ``canonical_weights``).
The reference reads that layout; the architecture's ``to_program`` hands
the same arrays to the program in the layout ``repro.models.api``
expects, as a checkpoint loader would, and ``check_program_layout`` holds
the result to the tree the program builds.
"""
from __future__ import annotations

import jax


def key_from_seed(seed: int):
    """A PRNG key from any non-negative whole number, also past 32 bits."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0x7FFFFFFF)


def check_program_layout(params, cfg):
    """Raise if ``params`` does not match the tree ``init_model`` builds
    (shapes and dtypes), so that a changed program layout fails loudly."""
    from repro.models.api import init_model

    want = jax.eval_shape(lambda k: init_model(k, cfg), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       params)
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
            zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"the program's parameter layout changed: expected "
                         f"{want}, built {got}")
