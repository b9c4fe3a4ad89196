"""Jit'd wrapper with impl dispatch.  ``probe`` accepts ANY probe-side
row count: the kernel pads the probe lane to whole tiles and slices the
positions back (padded lookups are discarded)."""
from ... import kernels
from .hash_join import join_probe
from .ref import join_probe_ref


def probe(left_hashes, right_hashes_sorted, *, impl: str = "ref",
          tile_n: int = 1024, interpret=None):
    if impl == "pallas":
        return join_probe(left_hashes, right_hashes_sorted, tile_n=tile_n,
                          interpret=kernels.resolve_interpret(interpret))
    return join_probe_ref(left_hashes, right_hashes_sorted)
