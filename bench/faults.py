"""Faults planted under the timed path: each wraps the trainer's jitted step.

They exist to show that the comparison which decides ``correct`` catches
what a broken training step would do.  A training cell on one chip can have
three: a step that returns its state unchanged, a step over half of the
batch (half the rows; half of each row where the batch is one row) with the
mean over the rest, and one answer altered where it is produced -- one
parameter leaf's update lost: a large one (the MLP down projections keep
their value), or a small one that Eva does not precondition (the first
norm's scales keep their value and their optimizer state reads zero; in
bfloat16 their change over three steps rounds away, so only the state
shows it).
"""
from __future__ import annotations


def _copy(tree):
    import jax
    return jax.tree_util.tree_map(lambda x: x + 0, tree)


def frozen(step):
    """The step runs (on copies) but its state comes back unchanged."""
    def run(params, opt_state, batch):
        _, _, metrics = step(_copy(params), _copy(opt_state), batch)
        return params, opt_state, metrics
    return run


def half_batch(step):
    """The step sees half of the batch and takes its mean over that half."""
    def run(params, opt_state, batch):
        rows, cols = batch['tokens'].shape
        cut = (lambda x: x[:rows // 2]) if rows >= 2 else \
            (lambda x: x[:, :cols // 2])
        return step(params, opt_state, {k: cut(v) for k, v in batch.items()})
    return run


def dropped(path: tuple):
    """The leaf at ``path`` keeps its old value."""
    def fault(step):
        def run(params, opt_state, batch):
            kept = params
            for k in path:
                kept = kept[k]
            kept = kept + 0
            new_params, new_state, metrics = step(params, opt_state, batch)
            d = new_params = dict(new_params)
            for k in path[:-1]:
                d[k] = dict(d[k])
                d = d[k]
            d[path[-1]] = kept
            return new_params, new_state, metrics
        return run
    return fault


def zeroed_state(path: tuple):
    """The leaf at ``path`` keeps its value, and every entry of the
    optimizer state under the same path comes back zero."""
    def fault(step):
        keep = dropped(path)(step)

        def run(params, opt_state, batch):
            import jax
            import jax.numpy as jnp
            new_params, new_state, metrics = keep(params, opt_state, batch)

            def zero(keys, x):
                tail = tuple(getattr(k, 'key', None) for k in keys[-len(path):])
                return jnp.zeros_like(x) if tail == path else x
            return (new_params,
                    jax.tree_util.tree_map_with_path(zero, new_state),
                    metrics)
        return run
    return fault


FAULTS = {'frozen': frozen, 'half_batch': half_batch,
          'dropped_leaf': dropped(('blocks', 'mlp', 'down', 'w')),
          'dropped_scale': zeroed_state(('blocks', 'norm1', 'scale'))}
