"""Device idle share: 1 minus the union of the TPU's operation intervals
over the traced window's length (%)."""


def read(rec):
    w = rec.reduced["window_s"]
    if w <= 0:
        return None
    return 100.0 * (1.0 - rec.reduced["busy_s"] / w)
