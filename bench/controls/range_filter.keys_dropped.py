"""range_filter.keys_dropped: the filter is rebuilt from all keys but the
last 1/128 of them, a lost insert: ``false_negatives``."""

FAILS = "false_negatives"


def fault(system) -> None:
    import jax.numpy as jnp

    f = system.filter
    keys = system.data.keys
    f.state = jnp.zeros_like(f.state)
    f.insert(keys[: len(keys) - len(keys) // 128])
    f.state.block_until_ready()
