"""KV bytes the transfer layer pulled per request that reached decode in
the window (`TransferManager.stats()["total_bytes"]`), in MB. Moves
`ttft_p90_ms`."""


def read(run):
    admitted = sum(s.admitted for s in run.steps)
    if admitted <= 0:
        return None
    return run.counters.get("tx.total_bytes", 0) / admitted / 1e6
