"""Operations and bytes from shapes, found by name from the benchmark's data
files; see ../README.md."""


def least_seconds(flops, nbytes, peaks):
    """(seconds, which bound): the least time the chip needs, the larger of
    operations over peak FLOP/s and bytes over peak bytes/s."""
    by_flops = flops / peaks["flops_per_s"]
    by_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (by_flops, "flops") if by_flops > by_bytes else (by_bytes, "bytes")
