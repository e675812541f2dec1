"""Peak device memory of the fullest chip, in GB: the larger of
``peak_bytes_in_use`` and ``peak_bytes_reserved`` (on a TPU the
compiled program's scratch is counted in the reserved bytes only)."""


def read(ctx):
    b = ctx["memory_peak_bytes"]
    return b / 1e9 if b else None
