"""Compile checks for a described TPU v5e chip, with no chip attached.

The TPU compiler refuses what the Pallas interpreter accepts: blocks not
aligned to the (8, 128) tiling, unsupported in-kernel ops, programs that
do not fit the chip.  These tests compile, for one chip of a described
``v5e:2x2`` topology at 2^22 rows, every Pallas kernel of the engine
and two whole PigMix job programs with the kernels on (L3's join job and
L6's group-by job), and check that each program calls its Mosaic kernel
and fits the chip's 16 GB.  Nothing runs.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU compiler's library, so only the
worker that runs this file may touch it.  The persistent compilation
cache is off around these compiles, as a described chip's entries cannot
be read back.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import kernels
from repro.dataflow import physical
from repro.dataflow.compiler import compile_workflow
from repro.dataflow.table import Table
from repro.roofline.analysis import peaks
from repro.workloads import pigmix

ROWS = 1 << 22
HBM_BYTES = peaks("TPU v5 lite")["hbm_bytes"]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def mosaic(monkeypatch):
    """Steer the engine as a TPU backend would: kernels on, compiled."""
    monkeypatch.setattr(physical, "_USE_PALLAS", True)
    monkeypatch.setattr(kernels, "interpret", lambda: False)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    return compiled.as_text(), total


def _kernel_calls(text):
    return [ln for ln in text.splitlines() if "tpu_custom_call" in ln]


def _kernel_case(name, chip):
    def s(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
    hashes, valid = s((ROWS,), jnp.uint32), s((ROWS,), jnp.bool_)
    if name == "segment_sum_sorted":
        from repro.kernels.segment_reduce.segment_reduce import \
            segment_sum_sorted
        return (lambda v, i: segment_sum_sorted(
            v, i, num_segments=ROWS, interpret=False),
            s((ROWS, 1), jnp.float32), s((ROWS,), jnp.int32))
    if name == "join_probe":
        from repro.kernels.hash_join.hash_join import (MAX_BUILD_ROWS,
                                                       join_probe)
        # the largest build side the operator hands the kernel
        return (lambda lh, rh: join_probe(lh, rh, interpret=False),
                hashes, s((MAX_BUILD_ROWS,), jnp.uint32))
    from repro.kernels.radix_partition import radix_partition as rp
    if name == "radix_partition":
        return (lambda h, v: rp.radix_partition(
            h, v, n_parts=8, tile_n=1024, interpret=False), hashes, valid)
    return (lambda h, v: rp.partition_scatter(
        h, v, n_parts=4, bucket=ROWS // 4, interpret=False), hashes, valid)


@pytest.mark.parametrize("name", ["segment_sum_sorted", "join_probe",
                                  "radix_partition", "partition_scatter"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, *args = _kernel_case(name, one_chip)
    text, total = _compile(fn, *args)
    calls = _kernel_calls(text)
    assert any(name in ln for ln in calls), f"{name} is not a Mosaic call"
    assert total < HBM_BYTES, total


def _table_shapes(table, rows, chip):
    """The same Table with every column as a ShapeDtypeStruct of
    ``rows`` rows on the described chip."""
    def spec(c):
        return jax.ShapeDtypeStruct((rows,) + c.shape[1:], c.dtype,
                                    sharding=chip)
    return Table({n: spec(c) for n, c in table.columns.items()},
                 spec(table.valid))


@pytest.mark.parametrize("query,kernel", [("L3", "join_probe"),
                                          ("L6", "segment_sum_sorted")])
def test_pigmix_job_compiles_for_v5e(one_chip, mosaic, query, kernel):
    job = compile_workflow(getattr(pigmix, query)()).jobs[0]
    sample = {"page_views": pigmix.gen_page_views(8),
              "users": pigmix.gen_users()}
    datasets = {n: _table_shapes(t, ROWS if n == "page_views"
                                 else t.capacity, one_chip)
                for n, t in sample.items()
                if n in {o.params["dataset"] for o in job.plan.loads()}}

    def run(ds):
        return physical.execute_plan(job.plan, ds)

    text, total = _compile(run, datasets)
    assert any(kernel in ln for ln in _kernel_calls(text)), \
        f"{query}'s job does not call {kernel}"
    assert total < HBM_BYTES, total
