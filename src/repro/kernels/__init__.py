"""Pallas kernels for the relational hot spots of the engine.

Every kernel compiles with Mosaic on a TPU backend and runs in the
Pallas interpreter on any other backend.  :func:`interpret` is the one
place that decides which: each ``ops.py`` wrapper asks it unless its
caller passes ``interpret`` explicitly, so no call site can interpret a
kernel on the chip by forgetting an argument.
"""
import jax
import jax.numpy as jnp

# rows are laid out lane-dense, (N/128, 128), and every kernel block is
# a whole number of (8, 128) int32/float32 tiles
LANES = 128
SUBLANES = 8
BLOCK_ROWS = LANES * SUBLANES


def interpret() -> bool:
    """True unless JAX's default backend is a TPU."""
    return jax.default_backend() != "tpu"


def resolve_interpret(flag):
    return interpret() if flag is None else bool(flag)


def block_rows(tile_n: int) -> int:
    """``tile_n`` rounded up to a whole number of (8, 128) tiles."""
    return -(-max(int(tile_n), 1) // BLOCK_ROWS) * BLOCK_ROWS


def lane_dense(a, n_rows: int, fill):
    """Pad a 1-D lane to ``n_rows`` with ``fill`` and fold it to
    ``(n_rows / 128, 128)``."""
    pad = n_rows - a.shape[0]
    if pad:
        a = jnp.concatenate([a, jnp.full((pad,), fill, a.dtype)])
    return a.reshape(n_rows // LANES, LANES)
