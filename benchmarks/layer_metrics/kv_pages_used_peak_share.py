"""Most KV pool pages in use at once over the window, as a share of the
allocatable pool (`kv.used_pages`, polled every 20 ms)."""


def read(obs):
    total = obs.counters.get("kv_pages_total")
    if not total:
        return None
    return 100.0 * obs.counters["kv_pages_peak"] / total
