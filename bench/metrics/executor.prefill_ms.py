"""Executor prefill: the benchmark's host span around each
`RealModelExecutor.prefill_request` call (batch-1 prefill and the cache
splice, ending in the host sync of its token), mean in ms."""


def read(rec):
    spans = rec.of("prefill")
    if not spans:
        return None
    return 1e3 * sum(e - s for _, s, e, _ in spans) / len(spans)
