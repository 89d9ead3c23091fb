"""Executor decode: the benchmark's host span around each
`RealModelExecutor.decode_step_real` call (the fused step and the host
sync of its tokens): total time over steps, in ms."""


def read(rec):
    spans = rec.of("decode")
    if not spans:
        return None
    return 1e3 * sum(e - s for _, s, e, _ in spans) / len(spans)
