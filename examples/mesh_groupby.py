"""Mesh-sharded execution with partition-aware reuse (DESIGN.md §11).

  1. Run a join + group-by on an 8-way device mesh.  Every blocking
     operator executes as a shard_map map->shuffle->reduce stage; the
     join's output artifact is stored as 8 per-partition shards,
     hash-partitioned on the grouping key.
  2. Run a second query over the same join.  The join is answered from
     the repository, and because the reused artifact is co-partitioned
     on the consumer's keys, the group-by runs SHUFFLE-FREE — reuse
     skips the exchange, not just the compute.

On an accelerator host the mesh spans the host's own devices, in this
one process.  On a CPU-only machine the script re-executes itself with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` so an 8-way mesh
exists there (the flag must be set before JAX starts its backend).

Usage: PYTHONPATH=src python examples/mesh_groupby.py
"""
import os
import subprocess
import sys

sys.path.insert(0, "src")

N_DEVICES = 8


def main():
    import jax

    from repro.core import plan as P
    from repro.core.restore import ReStore
    from repro.launch.mesh import make_data_mesh
    from repro.store.artifacts import ArtifactStore, Catalog
    from repro.workloads import pigmix

    n_dev = min(N_DEVICES, len(jax.devices()))
    mesh = make_data_mesh(n_dev)
    store = ArtifactStore()
    catalog = Catalog(store)
    pigmix.register_all(catalog, n_rows=1 << 13)
    restore = ReStore(catalog, store, heuristic="aggressive", mesh=mesh)

    def query(aggs, out):
        pv = P.project(P.load("page_views"), ["user", "estimated_revenue"])
        u = P.project(P.load("users"), ["name"])
        j = P.join(pv, u, ["user"], ["name"])
        return P.PhysicalPlan([P.store(P.groupby(j, ["user"], aggs), out)])

    print(f"=== Q1 on a {n_dev}-way mesh: join + group-by ===")
    _, rep1 = restore.run_plan(query(
        {"total": ("sum", "estimated_revenue")}, "q1_out"))
    for j in rep1.jobs:
        if j.stats:
            print(f"  job {j.job_id}: {j.stats.shuffles} exchanges, "
                  f"{j.stats.shuffles_skipped} skipped")
    parts = [(n, store.partitioning(n)) for n in store.names()
             if store.partitioning(n)]
    assert parts, "mesh run must record partition properties"
    n, p = parts[0]
    print(f"  artifact {n}: {p['n_parts']} shards on keys {p['keys']}")

    print("=== Q2: same join, different aggregates ===")
    _, rep2 = restore.run_plan(query(
        {"total": ("sum", "estimated_revenue"),
         "visits": ("count", "estimated_revenue")}, "q2_out"))
    skipped = sum(j.stats.shuffles_skipped for j in rep2.jobs if j.stats)
    print(f"  reused {rep2.n_reused} artifacts, "
          f"skipped {skipped} exchange(s)")
    assert rep2.n_reused > 0, "join must be answered from the repository"
    assert skipped > 0, \
        "co-partitioned reuse must skip the group-by exchange"
    print("mesh group-by example OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    if "--child" in sys.argv or jax.default_backend() != "cpu":
        main()
    else:
        env = dict(os.environ)
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={N_DEVICES}"
        env.setdefault("PYTHONPATH", "src")
        out = subprocess.run([sys.executable, os.path.abspath(__file__),
                              "--child"], env=env, cwd=os.getcwd())
        sys.exit(out.returncode)
