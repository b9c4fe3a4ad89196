"""Serving-time prefix-KV reuse through the unified repository
(DESIGN.md §17).

Walkthrough of the one-economics-engine serving stack, with every claim
asserted:

  1. cold prefill → snapshot stored as a ``kind="prefix"`` repository
     entry; a later prompt sharing the system prefix takes a
     subsumption hit and prefills only its suffix — bit-identical to a
     session without reuse
  2. multi-turn append: extending a stored prefix re-keys the entry in
     place (the §12 delta-refresh path) instead of storing a second
     snapshot
  3. tiering: snapshots demoted to the remote RSB1 blob tier are
     promoted back on use and still decode bit-identically

Usage: PYTHONPATH=src python examples/serve_prefix_reuse.py
"""
import sys
import tempfile

sys.path.insert(0, "src")

import numpy as np     # noqa: E402
import jax             # noqa: E402

from repro.configs import get_config                     # noqa: E402
from repro.models.api import build                       # noqa: E402
from repro.serve.kv_repo import KVRepository             # noqa: E402
from repro.serve.kv_store import KVTierStore             # noqa: E402
from repro.serve.session import ServeSession             # noqa: E402


def main():
    cfg = get_config("qwen3-1.7b", smoke=True)
    model = build(cfg)
    params = model.init(jax.random.PRNGKey(0))
    remote = tempfile.mkdtemp(prefix="kv_remote_")
    kv = KVRepository(model_version="demo-v1",
                      store=KVTierStore(remote_root=remote))
    sess = ServeSession(model, params, max_len=96, kv=kv)
    plain = ServeSession(model, params, max_len=96)

    rng = np.random.default_rng(0)
    system_prefix = rng.integers(1, cfg.vocab_size, 48)

    # -- 1. store, then subsumption hit ------------------------------
    total_prefilled = total_reused = 0
    for i in range(4):
        user_part = rng.integers(1, cfg.vocab_size, 16)
        prompt = np.concatenate([system_prefix, user_part])
        out, stats = sess.serve(prompt, n_decode=8)
        ref, _ = plain.serve(prompt, n_decode=8)
        assert (out == ref).all(), "reuse must not change outputs"
        total_prefilled += stats.prefilled_tokens
        total_reused += stats.reused_tokens
        print(f"request {i}: reused {stats.reused_tokens:3d} tokens, "
              f"prefilled {stats.prefilled_tokens:3d}")
    assert total_reused > 0, "later requests must hit the shared prefix"
    assert kv.stats()["semantic_hits"] > 0     # prefix-subsumption hits

    # -- 2. append-style extension rides the refresh path ------------
    first = np.concatenate([system_prefix,
                            rng.integers(1, cfg.vocab_size, 8)])
    sess2 = ServeSession(model, params, max_len=96, kv=kv, every_k=0)
    sess2.serve(first, n_decode=0)
    n_before = len(kv)
    turn2 = np.concatenate([first, rng.integers(1, cfg.vocab_size, 8)])
    hit = kv.probe(turn2)
    assert hit is not None and hit.length == len(first)
    hit = kv.splice(hit)
    _logits, cache = sess2._prefill(turn2, hit.cache, hit.length)
    entry = kv.extend(hit, turn2, cache)
    assert len(kv) == n_before               # re-keyed, not duplicated
    assert kv.repository.refreshes >= 1
    follow = kv.probe(turn2)
    assert follow is not None and follow.exact \
        and follow.entry is entry
    print(f"append extension: entry re-keyed in place "
          f"({kv.repository.refreshes} refreshes, {len(kv)} entries)")

    # -- 3. tier round-trip stays bit-identical ----------------------
    probe_prompt = np.concatenate(
        [system_prefix, rng.integers(1, cfg.vocab_size, 16)])
    warm_out, _ = sess.serve(probe_prompt, n_decode=8)
    for e in list(kv.entries.values()):
        kv.store.demote_to_remote(e.artifact)
    cold_out, st = sess.serve(probe_prompt, n_decode=8)
    assert (warm_out == cold_out).all(), "tier round-trip changed decode"
    assert st.reused_tokens > 0
    assert kv.store.stats["remote_hits"] > 0
    print(f"tier round-trip: {kv.store.stats['remote_hits']} remote "
          f"promotions, decode bit-identical")

    frac = total_reused / (total_reused + total_prefilled)
    print(f"repository: {len(kv)} prefix entries, "
          f"{kv.total_bytes >> 10} KiB under the shared budget; "
          f"prompt tokens answered from the repository: {frac:.0%}")
    print("serve_prefix_reuse OK")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
