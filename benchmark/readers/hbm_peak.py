"""Peak device memory on the fullest chip, in GB (10^9 bytes)."""


def read(rin):
    if not rin.memory_peak_bytes:
        return None
    return rin.memory_peak_bytes / 1e9
