"""Peak device memory in use after the window, on the fullest chip
(``memory_stats()["peak_bytes_in_use"]``)."""


def read(inp):
    return inp.peak_bytes / 2**30 if inp.peak_bytes else None
