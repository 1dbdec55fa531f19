"""Allocations the KV pool refused (`kv.stats()["alloc_failures"]`): each is
a request answered 429."""


def read(obs):
    return obs.counters.get("kv_alloc_failures")
